"""Faults planted in the timed path, and the control, for the tests that
show ``correct`` can come out false. The benchmark's own runs never apply
them; ``control.py`` and ``tests/`` do, through a loop's ``plant``.

Each loop kind plants, after its set-up, under the program it times:

* ``unchanged_state``: the step returns the state it was given (a
  coloring with nothing colored);
* ``half_batch``: half of the work is left out (half of the vertices'
  colors);
* ``altered_answer``: one answer is changed where it is produced (one
  vertex takes a neighbour's color);
* ``speculation_only`` (the control): the program with its conflict
  resolution cut to one round, the step a later PR would be tempted to
  drop: round 0's speculative colors are returned as they are.

A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations

import dataclasses

import numpy as np

FAULTS = ("unchanged_state", "half_batch", "altered_answer")
CONTROL = "speculation_only"


def alter(graph, colors: np.ndarray) -> np.ndarray:
    """``colors`` with the first vertex that has a neighbour given that
    neighbour's color."""
    colors = np.array(colors, copy=True)
    deg = np.diff(graph.row_ptr)
    v = int(np.flatnonzero(deg)[0])
    colors[v] = colors[graph.col_idx[graph.row_ptr[v]]]
    return colors


def replace_colors(report, colors):
    """``report`` with its colors replaced (and its cached count gone)."""
    report = dataclasses.replace(report, colors=colors)
    report.__dict__.pop("num_colors", None)
    return report


def speculation_only(plan):
    """A call of a plan of the same spec whose round loop stops after
    round 0, its unconverged result returned as it is."""
    from repro.core import compile_plan
    from repro.core import api
    cut = compile_plan(dataclasses.replace(plan.spec, max_rounds=1),
                       plan.statics)

    def call(g, **runtime):
        build = api._build_report

        def lenient(raw, *a, **k):
            return build(raw._replace(unconverged=np.bool_(False)), *a, **k)

        api._build_report = lenient
        try:
            return cut(g, **runtime)
        finally:
            api._build_report = build
    return call
