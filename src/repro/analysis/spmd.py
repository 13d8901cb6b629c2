"""Shared structure-walking for the SPMD verifier passes.

The distributed strategy's plan program is a ``shard_map`` mesh program
(one ``lax.while_loop`` of BSP rounds per device — core/distributed.py).
The three SPMD passes (:mod:`.collectives`, :mod:`.wirecost`,
:mod:`.halo`) all need the same two ingredients, which live here:

* :class:`SpmdGeometry` — the static mesh/envelope geometry the traced
  program was built for (``D``, ``Vl``, halo capacity, wire tier, packed
  color bound, frontier slab capacity). :func:`distributed_geometry`
  derives it from a spec/envelope exactly the way
  ``repro.analysis.trace_plan_program`` sizes the trace, so closed-form
  expectations and traced shapes are always about the *same* program;
* shard-program extraction — :func:`find_shard_jaxprs` locates every
  ``shard_map`` equation (the per-device program lives in its ``jaxpr``
  param) and :func:`collective_eqns` / :data:`COLLECTIVE_PRIMS` identify
  the cross-device communication points inside it.

Everything is pure jaxpr traversal: no execution, no compilation.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Tuple

from .jaxpr_walk import walk_eqns

# reductions over named axes. Under varying-axes typing ``lax.psum`` of a
# device-varying value traces as ``psum_invariant``.
REDUCING_PRIMS = frozenset({"psum", "psum_invariant", "pmin", "pmax"})

# cross-device communication primitives. axis_index is shard-VARYING but
# communicates nothing, so it is a uniformity source for the
# collective-safety pass, not a collective.
COLLECTIVE_PRIMS = REDUCING_PRIMS | frozenset({
    "all_gather", "ppermute", "all_to_all", "reduce_scatter", "pgather",
    "pbroadcast",
})

# collectives whose output is replicated (identical on every participating
# device), which is what makes a psum-derived vote a provably shard-uniform
# predicate.
REPLICATING_PRIMS = REDUCING_PRIMS | frozenset({"all_gather"})


@dataclasses.dataclass(frozen=True)
class SpmdGeometry:
    """Static geometry of one traced distributed mesh program.

    ``wire`` is the *resolved* tier ("boundary" | "full" — a spec's
    "auto" traces the boundary program; the spill program is the
    ``wire="full"`` sweep cell). ``boundary_cap`` is the halo slab width
    the traced program pinned (including the analyzer's floor-2 rule for
    capless envelopes), ``wire_colors`` the uncapped provable Delta+1
    bound sizing the packed payload.
    """

    num_devices: int
    verts_local: int
    edges_local: int
    boundary_cap: int
    wire: str
    wire_colors: int
    max_colors: int
    frontier_cap_v: int
    frontier_cap_e: int
    axis_names: Tuple[str, ...]

    @property
    def verts_global(self) -> int:
        return self.verts_local * self.num_devices


def distributed_geometry(spec, statics) -> SpmdGeometry:
    """The :class:`SpmdGeometry` of the program ``trace_plan_program``
    traces for this spec/envelope — one derivation shared by the tracer
    and every closed-form expectation, so they can never disagree about
    which program is under analysis."""
    import numpy as np
    from ..core.api import DistributedStrategy
    from ..core.frontier import frontier_capacities
    from ..core.graph import pad_bucket

    mesh = DistributedStrategy._mesh(spec)
    D = int(np.prod(mesh.devices.shape))
    V = int(statics.num_vertices)
    Vl = -(-V // D)
    slab = pad_bucket(int(-(-int(statics.padded_edges) // D) * 1.35))
    max_colors = int(statics.max_degree) + 1
    if spec.color_bound > 0:
        max_colors = min(max_colors, int(spec.color_bound))
    use_boundary = spec.wire != "full"
    # floor-2 rule: see trace_plan_program — the boundary program is traced
    # with a non-degenerate halo slab even for capless envelopes
    bcap = max(2, min(Vl, int(statics.boundary_cap))) if use_boundary else 1
    fcv = fce = 0
    if spec.frontier != "off":
        fcv, fce = frontier_capacities(
            Vl, slab, int(statics.max_degree),
            capacity=int(spec.frontier_capacity))
    return SpmdGeometry(
        num_devices=D, verts_local=Vl, edges_local=slab,
        boundary_cap=(bcap if use_boundary else 0),
        wire=("boundary" if use_boundary else "full"),
        wire_colors=int(statics.max_degree) + 1, max_colors=max_colors,
        frontier_cap_v=fcv, frontier_cap_e=fce,
        axis_names=tuple(mesh.axis_names))


def find_shard_jaxprs(closed_jaxpr) -> List[Tuple[object, object]]:
    """Every ``(shard_map_eqn, shard_body_jaxpr)`` in the program,
    including shard_maps nested under pjit wrappers."""
    found: List[Tuple[object, object]] = []

    def visit(eqn, enclosing):
        if eqn.primitive.name != "shard_map":
            return
        body = eqn.params.get("jaxpr")
        if hasattr(body, "jaxpr"):  # ClosedJaxpr
            body = body.jaxpr
        if body is not None:
            found.append((eqn, body))

    walk_eqns(closed_jaxpr.jaxpr, visit)
    return found


def mesh_axis_names(shard_eqn) -> Tuple[str, ...]:
    mesh = shard_eqn.params.get("mesh")
    names = getattr(mesh, "axis_names", None)
    return tuple(names) if names else ()


def eqn_axis_names(eqn) -> Tuple[str, ...]:
    """The named axes a collective equation communicates over."""
    axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, str))
    return (axes,) if isinstance(axes, str) else ()


def is_full_axis(eqn, mesh_axes: Tuple[str, ...]) -> bool:
    """True when the collective spans every mesh axis (its output is
    replicated across the whole device set)."""
    if eqn.params.get("axis_index_groups") is not None:
        return False
    names = eqn_axis_names(eqn)
    return bool(mesh_axes) and set(names) == set(mesh_axes)


def collective_eqns(jaxpr) -> List[object]:
    """Depth-first ordered collectives of ``jaxpr`` including sub-jaxprs
    (pjit bodies, nested cond branches in branch order) — the "ordered
    collective sequence" the branch-parity check compares."""
    out: List[object] = []
    walk_eqns(jaxpr, lambda eqn, enc: out.append(eqn)
              if eqn.primitive.name in COLLECTIVE_PRIMS else None)
    return out


def collective_signature(eqn) -> Tuple:
    """What must match across cond branches for the sequence to be
    deadlock-free: primitive, named axes, operand/result shapes+dtypes."""
    def avals(vs):
        return tuple((tuple(v.aval.shape), str(v.aval.dtype)) for v in vs)
    return (eqn.primitive.name, eqn_axis_names(eqn),
            avals(eqn.invars), avals(eqn.outvars))


def sub_jaxpr(param) -> Optional[object]:
    """The raw Jaxpr behind a params entry (ClosedJaxpr or Jaxpr)."""
    if hasattr(param, "jaxpr") and hasattr(param.jaxpr, "eqns"):
        return param.jaxpr
    if hasattr(param, "eqns"):
        return param
    return None


def cond_branches(eqn) -> List[object]:
    """Branch jaxprs of a ``cond`` eqn in branch-index order (index 0 =
    predicate false for the two-way boolean form)."""
    return [b for b in (sub_jaxpr(p) for p in eqn.params.get("branches", ()))
            if b is not None]


def while_parts(eqn):
    """``(cond_jaxpr, body_jaxpr, cond_nconsts, body_nconsts)``."""
    return (sub_jaxpr(eqn.params["cond_jaxpr"]),
            sub_jaxpr(eqn.params["body_jaxpr"]),
            int(eqn.params.get("cond_nconsts", 0)),
            int(eqn.params.get("body_nconsts", 0)))


def aval_elems(v) -> int:
    import numpy as np
    try:
        return int(np.prod(v.aval.shape)) if v.aval.shape else 1
    except Exception:
        return 0


def aval_nbytes(v) -> int:
    import numpy as np
    try:
        return aval_elems(v) * np.dtype(v.aval.dtype).itemsize
    except Exception:
        return 0


def iter_round_loops(shard_body) -> Iterator[object]:
    """The top-level ``while`` equations of the shard body — the BSP round
    loop(s). Nested fixpoint sweeps live inside and are NOT yielded."""
    for eqn in shard_body.eqns:
        if eqn.primitive.name == "while":
            yield eqn
        elif eqn.primitive.name == "pjit":
            sub = sub_jaxpr(eqn.params.get("jaxpr"))
            if sub is not None:
                yield from iter_round_loops(sub)
