"""DATAFLOW / DATAFLOWRECURSIVE — the paper's Algorithms 3-5, adapted to TPU.

The XMT version blocks each vertex's thread on ``readff(color[w])`` for every
smaller-index neighbor ``w`` — hardware dataflow over the dependency DAG
``w -> v  iff  (v,w) in E and w < v``. A TPU has no full/empty bits, so we
execute the *same DAG* as a chaotic fixpoint iteration of the dataflow
equations (DESIGN.md §2):

    c[v] <- mex{ c[w] : w in adj(v), w < v }     (uncolored w contributes 0)

All vertices update in parallel each sweep; vertices of dataflow level L hold
their final value after L sweeps (level = longest dependency path), so the
iteration converges in ``depth(DAG)`` sweeps to **exactly** the serial greedy
coloring in index order — the same invariant the XMT algorithm guarantees
(priority = vertex index, conceptually Jones-Plassmann). Deadlock-freedom is
structural: levels are computed by iteration, not discovered by blocking, so
DATAFLOWRECURSIVE's ``int_fetch_add`` recursion is unnecessary.

DATAFLOW is ITERATIVE's phase 1 in the fully-concurrent limit with
index-precedence (offset = vertex id): the sweep itself is the shared
:func:`repro.core.engine.fixpoint_sweep`, and the first-fit inner loop is
pluggable via ``engine=`` exactly as in iterative.py.

:func:`dataflow_levels` exposes the DAG depth / wavefront profile — the
"available parallelism" the XMT's 16K threads would have exploited.

Under the frontier layer (repro.core.frontier) the fixpoint runs
*active-set sweeps*: a vertex's iterate can change at sweep s only if one
of its dependencies changed at sweep s-1, so once the changed set fits the
static slab each sweep compacts ``dependents(changed)`` and re-evaluates
only those — same iterates, same sweep count, O(active) per sweep.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from jax import lax

from .engine import (EngineSpec, SweepSpec, fixpoint_iterate, fixpoint_sweep)
from .frontier import compact_frontier, frontier_counts
from .graph import DeviceGraph


@dataclasses.dataclass
class DataflowResult:
    colors: jnp.ndarray  # [V] int32, >= 1 — identical to serial greedy
    sweeps: int          # fixpoint sweeps == dataflow DAG depth (+1 check)

    @functools.cached_property
    def num_colors(self) -> int:
        from .metrics import num_colors as _distinct
        return _distinct(self.colors)


@functools.partial(jax.jit,
                   static_argnames=("max_sweeps", "backend", "color_bound",
                                    "frontier_cap_v", "frontier_cap_e"))
def _dataflow_impl(g: DeviceGraph, *, max_sweeps: int, backend,
                   color_bound: int = 0, frontier_cap_v: int = 0,
                   frontier_cap_e: int = 0):
    V = g.num_vertices
    max_colors = g.max_degree + 1
    if color_bound > 0:
        max_colors = min(max_colors, color_bound)
    mex = backend.bind(num_vertices=V, max_colors=max_colors,
                       ell_slot=g.ell_slot, ell_width=g.ell_width,
                       max_degree=g.max_degree)
    # dependency edges: only smaller-index neighbors forbid a color
    dep = g.dst < g.src  # padding (src == dst == V) excluded
    spec = SweepSpec(key_v=jnp.where(dep, g.src, V),
                     dyn_idx=g.dst, dyn=dep,
                     static_c=jnp.zeros_like(g.dst))
    use_frontier = frontier_cap_v > 0 and g.has_frontier
    if not use_frontier:
        colors, n, changed = fixpoint_sweep(
            mex, spec, jnp.zeros((V,), jnp.int32), jnp.ones((V,), jnp.bool_),
            max_sweeps=max_sweeps)
        return colors, n, changed, jnp.asarray(0, jnp.int32)

    # Frontier (active-set) sweeps. Vertex v's chaotic iterate can change at
    # sweep s only if one of its dependencies (smaller-index neighbors)
    # changed at sweep s-1, so the set that needs re-evaluating is exactly
    # dependents(changed) — everything else would recompute its own value.
    # Per sweep: compact the changed vertices' rows to find the dependents,
    # compact the dependents' rows, run the mex over that slab. Both sets
    # spill to the full sweep when they overflow the static capacities, so
    # iterates (and the sweep count) stay bit-identical to the full path.
    # A binned ELL layout's hub rows take the per-edge path in the slab
    # too (repro.core.frontier.compact_frontier).
    hub = g.hub_width if backend.needs_ell else 0
    mex_slab = backend.bind_slab(
        capacity=frontier_cap_v, max_colors=max_colors,
        ell_width=hub or g.max_degree, max_degree=g.max_degree)
    cap_v, cap_e = frontier_cap_v, frontier_cap_e

    def full_sweep(cpad):
        key_c = jnp.where(dep, cpad[spec.dyn_idx], spec.static_c)
        new = mex(spec.key_v, key_c)
        changed = new != cpad[:V]
        return cpad.at[:V].set(new), changed, jnp.asarray(0, jnp.int32)

    def active_sweep(args):
        cpad, chg = args
        # dependents of the changed set: one compaction of the changed rows
        dslab = compact_frontier(chg, g.inc_ptr, g.dst, cap_v, cap_e)
        dep_e = (dslab.src < V) & (dslab.dst > dslab.src)
        active = (jnp.zeros((V,), jnp.bool_)
                  .at[dslab.dst].max(dep_e, mode="drop"))
        nv, ne = frontier_counts(active, g.inc_ptr)

        def slab_sweep(cpad):
            slab = compact_frontier(active, g.inc_ptr, g.dst, cap_v, cap_e,
                                    hub_width=hub)
            forb = (slab.src < V) & (slab.dst < slab.src)
            key_c = jnp.where(forb, cpad[slab.dst], 0)
            mexv = mex_slab(jnp.where(forb, slab.owner, cap_v), key_c,
                            slab.slot)
            live = slab.vert < V
            old = cpad[jnp.minimum(slab.vert, V)]
            chg_new = (jnp.zeros((V,), jnp.bool_)
                       .at[jnp.minimum(slab.vert, V)]
                       .max(live & (mexv != old), mode="drop"))
            cpad = cpad.at[jnp.where(live, slab.vert, V + 1)].set(
                mexv, mode="drop")
            return cpad, chg_new, jnp.asarray(1, jnp.int32)

        return lax.cond((nv <= cap_v) & (ne <= cap_e),
                        slab_sweep, full_sweep, cpad)

    def body(state):
        cpad, chg, n, _, nslab = state
        nc, nce = frontier_counts(chg, g.inc_ptr)
        fits = (n > 0) & (nc <= cap_v) & (nce <= cap_e)
        cpad, chg, used = lax.cond(
            fits, active_sweep, lambda a: full_sweep(a[0]), (cpad, chg))
        still = jnp.any(chg)
        return cpad, chg, n + 1, still, nslab + used

    def cond(state):
        _, _, n, still, _ = state
        return jnp.logical_and(still, n < max_sweeps)

    init = (jnp.zeros((V + 1,), jnp.int32), jnp.ones((V,), jnp.bool_),
            jnp.asarray(0, jnp.int32), jnp.asarray(True),
            jnp.asarray(0, jnp.int32))
    cpad, _, n, still, nslab = lax.while_loop(cond, body, init)
    return cpad[:V], n, still, nslab


def color_dataflow(g, max_sweeps: int = 4096,
                   engine: EngineSpec = "sort",
                   color_bound: int = 0, model: str = "d1") -> DataflowResult:
    """``color_bound`` caps the table backends' capacity below Delta+1 —
    a caller-asserted bound, as in :func:`color_iterative`.

    ``model`` selects the coloring semantics ("d1" | "d2" | "pd2"), lowered
    exactly as in :func:`color_iterative`; under "d2"/"pd2" the fixpoint
    reproduces the *serial D2/PD2 greedy* in index order
    (:func:`repro.core.greedy_ref.greedy_color_d2` / ``greedy_color_pd2``),
    since the lowering is index-preserving.

    Back-compat shim over the registered ``"dataflow"``
    :class:`repro.core.api.ColoringStrategy` — same arguments, same
    bit-exact results, legacy :class:`DataflowResult` return. Prefer
    ``repro.core.color(g, strategy="dataflow", ...)`` or
    ``repro.core.compile_plan`` for compile-once reuse."""
    from .api import ColoringSpec, get_strategy  # lazy: api imports us
    spec = ColoringSpec(strategy="dataflow", model=model, engine=engine,
                        max_sweeps=max_sweeps, color_bound=int(color_bound))
    raw = get_strategy("dataflow").oneshot(spec, g)
    if bool(raw.unconverged):
        raise RuntimeError(f"DATAFLOW did not converge in {max_sweeps} sweeps")
    return DataflowResult(colors=raw.colors,
                          sweeps=int(raw.sweeps_per_round[0]))


@functools.partial(jax.jit, static_argnames=("num_vertices", "max_iters"))
def _levels_impl(src, dst, *, num_vertices: int, max_iters: int):
    V = num_vertices
    dep = dst < src

    def step(lv):
        lpad = jnp.concatenate([lv, jnp.zeros((1,), jnp.int32)])
        contrib = jnp.where(dep, lpad[dst], 0)
        seg = (
            jnp.zeros((V,), jnp.int32)
            .at[src].max(contrib, mode="drop")
        )
        return seg + 1

    lv, n, _ = fixpoint_iterate(step, jnp.ones((V,), jnp.int32),
                                max_iters=max_iters)
    return lv, n


def dataflow_levels(g: DeviceGraph, max_iters: int = 4096):
    """Dataflow level of each vertex (longest dependency chain ending at it).

    Returns (levels [V] int32 >= 1, depth). Wavefront L's vertices are
    pairwise independent — the paper's XMT threads resolve exactly this
    schedule through full/empty-bit blocking.
    """
    lv, _ = _levels_impl(g.src, g.dst, num_vertices=g.num_vertices, max_iters=max_iters)
    return lv, int(lv.max())
