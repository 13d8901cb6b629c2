"""What every traffic loop shares. A traffic file's ``kind`` names a loop
of its own, ``kinds/<kind>.py`` (found by ``cells.loop_class``); its other
keys are the loop's parameters, so a new mix of a known kind is a data
file alone, and a new kind is a new file.

A loop is closed: the client sends its next request once the last one has
come back, as a solver does. A loop class takes
``(config, traffic, seed)`` and has three steps, of which the harness times
only the middle one:

* ``setup()``: build the graphs, compile and warm every program the window
  runs; it leaves ``phases``, ``described`` and ``errors``;
* ``window(seconds, annotate)``: run whole passes over the loop's fixed
  work back to back, starting one only while the last one's duration says
  it ends inside ``seconds`` (:func:`closed_loop`); returns a
  :class:`Record`;
* ``check(record)``: once the window has closed, judge every answer with
  the plain reference (``reference.py``); returns each number compared
  with its limit.

Its ``plant(fault)`` breaks the program under the timed path, for the
control and the tests (``faults.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional


def closed_loop(step: Callable[[int], None], seconds: float, per_pass: int,
                clock: Callable[[], float] = time.perf_counter) -> tuple:
    """Run passes of ``per_pass`` calls ``step(i)`` back to back while the
    next pass is expected to end inside ``seconds`` (the last pass's
    duration is the estimate). At least one pass always runs, and the
    window ends only where a pass ends, so a faster program does more
    whole passes over the same work and never a part of one. Returns
    ``(calls, seconds from the window's start to the last call's end)``:
    every call's time counts, a stall included."""
    t0 = last = clock()
    n = 0
    while True:
        for _ in range(per_pass):
            step(n)
            n += 1
        t = clock()
        estimate, last = t - last, t
        if (t - t0) + estimate > seconds:
            return n, last - t0


def envelope(config: dict):
    """The plan's static envelope for the configuration's whole graph
    family: its vertex count, room for every raw pair as an edge
    (``2 * edge_factor * 2**scale`` directed entries, an upper bound on
    any seed's graph), and the configuration's max-degree bound. Every
    graph then runs through one compiled program, so only a checkout's
    first run compiles."""
    from repro.core import PlanShape
    V = 1 << int(config["scale"])
    return PlanShape(num_vertices=V,
                     padded_edges=2 * int(config["edge_factor"]) * V,
                     max_degree=int(config["plan_max_degree"]))


def spec_of(config: dict, **overrides):
    from repro.core import ColoringSpec
    fields = dict(strategy=config["strategy"], engine=config["engine"],
                  concurrency=int(config["concurrency"]),
                  max_rounds=int(config["max_rounds"]))
    fields.update(overrides)
    return ColoringSpec(**fields)


class Phases:
    """Seconds spent in each named step of a set-up, in order."""

    def __init__(self):
        self.phases: Dict[str, float] = {}
        self._t = time.perf_counter()

    def mark(self, name: str) -> float:
        t = time.perf_counter()
        self.phases[name] = t - self._t
        self._t = t
        return self.phases[name]


@dataclasses.dataclass
class Record:
    """What a run leaves for the per-layer readers and the result line."""
    config: dict
    num_vertices: int = 0
    attempted: int = 0
    failed: int = 0
    window_s: float = 0.0
    # one entry per coloring in the window
    colorings: List[dict] = dataclasses.field(default_factory=list)
    # plan traces taken inside the window
    retraces: int = 0
    reduction: Optional[object] = None      # trace_reduce.Reduction
    peaks: Optional[dict] = None
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    # (graph, seconds the program reported) per answer, for the log
    calls: List[tuple] = dataclasses.field(default_factory=list)
