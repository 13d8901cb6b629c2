"""Shared jaxpr traversal + provenance analysis for the static analyzer.

Everything here is pure structure-walking over ``jax.make_jaxpr`` output —
no execution, no compilation. Three facilities:

* :func:`walk_eqns` — depth-first over every equation including the
  sub-jaxprs of ``while``/``cond``/``scan``/``pjit``/``pallas_call``
  (params holding Jaxpr, ClosedJaxpr, or tuples of either);
* :func:`static_vars` — per-jaxpr dataflow: the set of variables derivable
  from literals/constants alone (primitives are pure, so an equation whose
  inputs are all static produces static outputs; ``iota`` has no inputs and
  is static by construction). A variable fed by the jaxpr's *inputs* — real
  data, or a loop carrier inside a ``while`` body — is never static. This
  is what lets the race classifier tell a slice-assignment scatter from a
  data-driven one;
* :func:`site_of` — ``<package-relative file>:<function>`` provenance of an
  equation from its source info (line numbers dropped: fingerprints must
  survive unrelated edits).
"""
from __future__ import annotations

import os
from typing import Callable, Iterator, List, Set

import numpy as np

from jax._src import core as jax_core
from jax._src import source_info_util

Literal = jax_core.Literal


def _sub_jaxprs(eqn) -> Iterator:
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            # ClosedJaxpr first: it forwards .eqns, but only the raw
            # Jaxpr carries .constvars for the provenance analysis
            if hasattr(x, "jaxpr") and hasattr(getattr(x, "jaxpr"),
                                               "eqns"):  # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):                     # raw Jaxpr
                yield x


def walk_eqns(jaxpr, visit: Callable) -> None:
    """Depth-first visit of every eqn; ``visit(eqn, enclosing_jaxpr)``."""
    for eqn in jaxpr.eqns:
        visit(eqn, jaxpr)
        for sub in _sub_jaxprs(eqn):
            walk_eqns(sub, visit)


def static_vars(jaxpr) -> Set:
    """Variables of ``jaxpr`` (one level, not sub-jaxprs) that depend on
    literals/constvars only — see module docstring."""
    static = set(jaxpr.constvars)
    for eqn in jaxpr.eqns:
        if all(isinstance(v, Literal) or v in static for v in eqn.invars):
            static.update(eqn.outvars)
    return static


def is_static(var, static: Set) -> bool:
    return isinstance(var, Literal) or var in static


def producer_map(jaxpr) -> dict:
    """outvar -> producing eqn (one jaxpr level)."""
    prod = {}
    for eqn in jaxpr.eqns:
        for o in eqn.outvars:
            prod[o] = eqn
    return prod


_FILL_PRESERVING = frozenset({
    "broadcast_in_dim", "convert_element_type", "reshape", "copy",
    "squeeze", "expand_dims",
    "pvary",  # re-tags the varying mesh axes; the values are unchanged
})


def is_constant_fill(var, jaxpr, _prod=None, _depth=0) -> bool:
    """True when ``var`` is provably a constant-filled array (every element
    equal): a literal, or a fill-preserving chain over one. The idempotence
    test for overlapping stores — colliding writes of the same constant
    commute."""
    if isinstance(var, Literal):
        val = np.asarray(var.val)
        return val.size <= 1 or bool((val == val.flat[0]).all())
    if _depth > 8:
        return False
    if _prod is None:
        _prod = producer_map(jaxpr)
    eqn = _prod.get(var)
    if eqn is None or eqn.primitive.name not in _FILL_PRESERVING:
        return False
    data_ins = [v for v in eqn.invars]
    return bool(data_ins) and all(
        is_constant_fill(v, jaxpr, _prod, _depth + 1) for v in data_ins)


def site_of(eqn, fallback: str = "unknown:unknown") -> str:
    """Stable ``file:function`` provenance of an equation."""
    try:
        frame = source_info_util.user_frame(eqn.source_info.traceback)
    except Exception:
        frame = None
    if frame is None:
        return fallback
    # the innermost name only: fingerprints stay "file:function" whether
    # the frame reports a bare or a qualified ("outer.<locals>.f") name
    func = frame.function_name.rsplit(".", 1)[-1]
    return f"{rel_source_path(frame.file_name)}:{func}"


def rel_source_path(path: str) -> str:
    """Package-relative source path: '.../src/repro/core/engine.py' ->
    'core/engine.py'; files outside the package keep their basename."""
    norm = path.replace(os.sep, "/")
    marker = "/repro/"
    if marker in norm:
        return norm.rsplit(marker, 1)[1]
    return norm.rsplit("/", 1)[-1]


def aval_bytes(aval) -> int:
    try:
        size = int(np.prod(aval.shape)) if aval.shape else 1
        return size * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def collect_consts(closed_jaxpr) -> List[np.ndarray]:
    """Concrete constants captured by the trace (closure-captured arrays)."""
    out = []
    for c in getattr(closed_jaxpr, "consts", ()):
        try:
            out.append(np.asarray(c))
        except Exception:
            continue
    return out
