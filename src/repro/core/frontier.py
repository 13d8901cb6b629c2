"""The frontier execution layer: active-set compaction for speculation rounds.

The paper's central empirical fact (§5, Fig. 10) is that after the first
speculation round the pending set collapses to a tiny conflicted tail —
typically well under 1% of |V| — yet a naive SIMD driver keeps sweeping the
full padded edge list every round. Rokos et al. (arXiv:1505.04086) show that
recoloring only the conflicted frontier is where the multi-core speedup
comes from; the distributed-GPU line (Bogle et al., arXiv:2107.00075) uses
the same active-set compaction to bound communication. This module is that
mechanism, shared by all three strategies:

* :func:`frontier_capacities` — the static bucket ladder: slab capacities
  derived from the graph envelope via :func:`repro.core.graph.pad_bucket`,
  so shapes stay static under ``jit``/``while_loop`` and a
  :class:`repro.core.api.ColoringPlan` keeps its zero-retrace guarantee.
* :func:`compact_frontier` — ``lax.sort``-free cumsum-scatter compaction of
  the active vertices AND their incident constraint edges into a
  fixed-capacity :class:`FrontierSlab`, one CSR gather (the DeviceGraph's
  ``inc_ptr`` auxiliary; the distributed driver derives per-shard pointers
  on device).
* :func:`frontier_sweep` — the speculation inner loop over the slab only:
  each sweep costs O(cap_e + cap_v·C) instead of O(E + V·C). Bit-identical
  to :func:`repro.core.engine.fixpoint_sweep` on the full edge list, because
  the slab carries *every* constraint edge incident to an active vertex and
  inactive vertices cannot change.
* :func:`frontier_conflicts` — Alg. 2 phase 2 over the slab edges only.

Spill semantics: capacities are static, frontiers are data. Every round the
driver checks the actual active counts against the slab capacities and falls
back to the full-edge sweep when the frontier overflows (``lax.cond``), so
results are bit-identical in ALL regimes — the slab is purely an execution
bypass. Round 0 (everything pending) always takes the full path.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax.numpy as jnp
from jax import lax

from .engine import SlabMexFn
from .graph import pad_bucket

_INT32_MAX = jnp.iinfo(jnp.int32).max

FRONTIER_MODES = ("auto", "on", "off")


def frontier_capacities(num_vertices: int, padded_edges: int,
                        max_degree: int = 0, *,
                        capacity: int = 0) -> Tuple[int, int]:
    """The static bucket ladder: (vertex capacity, edge capacity) slabs.

    Defaults size the vertex slab at ~|V|/32 and the edge slab at the
    matching average-degree share with 2x skew headroom (never below one
    full max-degree row, so a single conflicted hub does not force a
    spill), both rounded up the :func:`repro.core.graph.pad_bucket` ladder
    so plan envelopes stay quantized. ``capacity`` overrides the vertex
    capacity (the ``ColoringSpec.frontier_capacity`` knob); the edge slab
    scales with it. All inputs are static envelope values — same envelope,
    same capacities, zero retrace. A degenerate envelope (V=0 or E=0) has
    nothing to compact and gets ``(0, 0)`` — frontier disabled — instead
    of a phantom minimum-bucket slab."""
    if int(num_vertices) <= 0 or int(padded_edges) <= 0:
        return 0, 0
    V = max(1, int(num_vertices))
    E = max(1, int(padded_edges))
    cap_v = int(capacity) if capacity > 0 else max(64, V // 32)
    cap_v = pad_bucket(min(cap_v, V), min_bucket=8)
    avg_share = (2 * E // V) * cap_v  # cap_v rows of twice-average degree
    cap_e = max(cap_v, avg_share, 2 * max(0, int(max_degree)))
    cap_e = pad_bucket(min(cap_e, E), min_bucket=8)
    return cap_v, cap_e


def resolve_frontier(mode: str, capacity: int, *, num_vertices: int,
                     padded_edges: int, max_degree: int,
                     has_inc: bool) -> Tuple[int, int]:
    """Resolve a spec-level ``frontier=`` knob against a concrete graph
    envelope into static slab capacities ((0, 0) = frontier disabled).

    ``"auto"`` enables the frontier whenever the graph carries the
    incident-edge auxiliary (``DeviceGraph.inc_ptr``; wedge-lowered
    multisets do not — their edge space is not row-deduped); ``"on"``
    demands it and raises otherwise; ``"off"`` disables."""
    if mode not in FRONTIER_MODES:
        raise ValueError(f"unknown frontier mode {mode!r}; "
                         f"choose from {FRONTIER_MODES}")
    usable = has_inc and padded_edges > 0 and num_vertices > 0
    if mode == "off":
        return 0, 0
    if not usable:
        if mode == "on":
            raise ValueError(
                "frontier='on' needs the incident-edge auxiliary: build the "
                "graph via Graph.to_device() (any layout attaches inc_ptr) "
                "— wedge-lowered d2/pd2 multisets don't carry it, use "
                "lowering='square'")
        return 0, 0
    return frontier_capacities(num_vertices, padded_edges, max_degree,
                               capacity=capacity)


class FrontierSlab(NamedTuple):
    """The compacted active set: ``cap_v`` vertex rows + ``cap_e`` incident
    edges, fixed shapes, padded with inert sentinels.

    vert:  [cap_v] int32 vertex id of each slab row; ``V`` = empty row.
    owner: [cap_e] int32 slab row owning each slab edge; ``cap_v`` = pad.
    src:   [cap_e] int32 vertex id of the owning row (= vert[owner]);
           ``V`` = pad.
    dst:   [cap_e] int32 edge target in the *original* dst id space
           (global ids under the distributed driver); ``dst_pad`` = pad.
    slot:  [cap_e] int32 position of the edge within its row (the ELL slot
           the ``ell_pallas`` slab bind scatters through); in a binned
           slab, the marker ``hub_width`` on every edge of a hub row.
    nv/ne: scalar int32 true active counts — may EXCEED the capacities;
           callers must spill to the full path when they do.
    """

    vert: jnp.ndarray
    owner: jnp.ndarray
    src: jnp.ndarray
    dst: jnp.ndarray
    slot: jnp.ndarray
    nv: jnp.ndarray
    ne: jnp.ndarray


def frontier_counts(active: jnp.ndarray,
                    inc_ptr: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(nv, ne) of an active mask — the O(V) spill check, computable without
    building the slab."""
    deg = inc_ptr[1:] - inc_ptr[:-1]
    nv = active.sum(dtype=jnp.int32)
    ne = jnp.where(active, deg, 0).sum(dtype=jnp.int32)
    return nv, ne


def compact_frontier(active: jnp.ndarray, inc_ptr: jnp.ndarray,
                     dst: jnp.ndarray, cap_v: int, cap_e: int,
                     *, dst_pad: Optional[int] = None,
                     hub_width: int = 0) -> FrontierSlab:
    """Compact the active vertices and their incident CSR rows into a
    :class:`FrontierSlab` — no sort: a rank cumsum places vertices, a
    degree cumsum + scatter + running max assigns edges to rows, and one
    gather through ``inc_ptr`` pulls the edge targets.

    ``active``  [V] bool; ``inc_ptr`` [V+1] int32 row pointers into ``dst``
    (rows must be contiguous — true of every ``Graph.to_device`` edge list
    and of ``partition_graph`` slabs); ``dst`` the edge-target array the
    rows index; ``dst_pad`` the sentinel for padded slab edges (defaults to
    V — the distributed driver passes its global phantom id instead);
    ``hub_width`` > 0 bins the slab as the graph's ELL layout is binned
    (:attr:`repro.core.graph.DeviceGraph.hub_width`): every edge of a row
    longer than it gets the slot ``hub_width``, the hub path's marker.

    Overflow never corrupts: rows landing beyond the capacities are dropped
    by the scatters, and ``nv``/``ne`` report the TRUE counts so callers
    spill to the full path.
    """
    V = active.shape[0]
    fill = V if dst_pad is None else int(dst_pad)
    act32 = active.astype(jnp.int32)
    deg = inc_ptr[1:] - inc_ptr[:-1]
    nv = act32.sum()
    ne = jnp.where(active, deg, 0).sum(dtype=jnp.int32)

    # vertices: rank-within-active-set IS the slab row (order-preserving)
    rank = jnp.cumsum(act32) - 1
    vert = (jnp.full((cap_v,), V, jnp.int32)
            .at[jnp.where(active, rank, cap_v)]
            .set(jnp.arange(V, dtype=jnp.int32), mode="drop"))

    # edges: exclusive cumsum of slab-row degrees gives each row's start;
    # scatter row ids at the starts, running max floods them rightwards
    degp = jnp.concatenate([deg, jnp.zeros((1,), jnp.int32)])
    vdeg = degp[jnp.minimum(vert, V)]            # empty rows contribute 0
    starts = jnp.cumsum(vdeg) - vdeg
    owner = (jnp.zeros((cap_e,), jnp.int32)
             .at[jnp.where(vdeg > 0, starts, cap_e)]
             .max(jnp.arange(cap_v, dtype=jnp.int32), mode="drop"))
    owner = lax.cummax(owner)
    eidx = jnp.arange(cap_e, dtype=jnp.int32)
    valid = eidx < jnp.minimum(ne, cap_e)
    slot = eidx - starts[owner]
    src = vert[owner]                            # [cap_e], V on empty rows
    gidx = inc_ptr[jnp.minimum(src, V)] + slot   # src <= V indexes [V+1] ptr
    if dst.shape[0]:
        gdst = dst[jnp.clip(gidx, 0, dst.shape[0] - 1)]
    else:  # no edges: every slab edge is padding, and there is no
        gdst = jnp.full((cap_e,), fill, jnp.int32)  # element to gather
    if hub_width:
        slot = jnp.where(vdeg[owner] > hub_width, hub_width, slot)
    return FrontierSlab(
        vert=vert,
        owner=jnp.where(valid, owner, cap_v),
        src=jnp.where(valid, src, V),
        dst=jnp.where(valid, gdst, fill),
        slot=jnp.where(valid, slot, 0),
        nv=nv, ne=ne)


def frontier_sweep(mex_slab: SlabMexFn, *, key_v: jnp.ndarray,
                   dyn: jnp.ndarray, dyn_idx: jnp.ndarray,
                   static_c: jnp.ndarray, slot: jnp.ndarray,
                   write_vert: jnp.ndarray, cpad0: jnp.ndarray,
                   max_sweeps: int, wrap=lambda x: x):
    """The speculation inner loop over a compacted slab: chaotic sweeps of
    ``c[vert[i]] <- mex{ contribution(e) : e in row i }`` to a fixpoint.

    Mirrors :func:`repro.core.engine.fixpoint_sweep` in slab space — same
    contribution classification (``dyn`` re-reads the live padded color
    vector at ``dyn_idx``, else the frozen ``static_c``), same convergence
    rule, so sweep counts and fixpoints are bit-identical to the full-edge
    path. ``cpad0`` is the padded color carrier ([V+1]; the trailing 0 is
    the phantom gather target); ``write_vert`` the cpad index of each slab
    row, with any value >= len(cpad)-1 treated as an inert row.

    Returns ``(cpad, sweeps, still_changing)``.
    """
    n_pad = cpad0.shape[0]                       # V + 1
    widx = jnp.where(write_vert < n_pad - 1, write_vert, n_pad)
    wok = write_vert < n_pad - 1

    def body(state):
        cpad, _, n = state
        key_c = jnp.where(dyn, cpad[dyn_idx], static_c)
        mexv = mex_slab(key_v, key_c, slot)
        old = cpad[jnp.minimum(widx, n_pad - 1)]
        changed = jnp.any(wok & (mexv != old))
        return cpad.at[widx].set(mexv, mode="drop"), changed, n + 1

    def cond(state):
        _, changed, n = state
        return jnp.logical_and(changed, n < max_sweeps)

    cpad, changed, n = lax.while_loop(
        cond, body,
        (cpad0, wrap(jnp.asarray(True)), wrap(jnp.asarray(0, jnp.int32))))
    return cpad, n, changed


def frontier_conflicts(slab: FrontierSlab, cpad: jnp.ndarray,
                       ppad: jnp.ndarray, num_vertices: int) -> jnp.ndarray:
    """Alg. 2 phase 2 over the slab edges only — the frontier counterpart of
    :func:`repro.core.engine.speculation_conflicts`. Exact, because every
    conflict edge has a pending ``src`` and the slab holds ALL edges
    incident to pending vertices. Returns the next round's pending mask
    ([V] bool)."""
    conf_e = (ppad[jnp.minimum(slab.dst, num_vertices)]
              & (cpad[jnp.minimum(slab.src, num_vertices)]
                 == cpad[jnp.minimum(slab.dst, num_vertices)])
              & (slab.src > slab.dst))
    return (jnp.zeros((num_vertices,), jnp.int32)
            .at[slab.src].max(conf_e.astype(jnp.int32), mode="drop")
            .astype(jnp.bool_))
