"""ITERATIVE — the paper's Algorithm 2 (speculation + iteration), vectorized.

Execution model (faithful adaptation, DESIGN.md §2)
---------------------------------------------------
The paper runs Alg. 2's phase-1 loop with ``#pragma omp parallel for`` and
default *static* scheduling: each of ``P`` threads owns a contiguous block of
the pending set and colors it sequentially. In the canonical lockstep
("superstep") model of that execution, the vertices racing at any instant are
those at the same *offset* within their thread's block; a vertex sees the
committed colors of every vertex at a strictly smaller offset, and conflicts
can only arise between same-offset vertices.

We reproduce those semantics exactly on a SIMD machine. Per round:

  1. pending vertices get ``offset = rank % ceil(|U|/P)`` (rank = position in
     the pending set, matching OpenMP-static block assignment) —
     :func:`repro.core.engine.lockstep_offsets`;
  2. tentative colors are the fixpoint of the *dataflow equations over the
     offset-precedence DAG* —
         c[v] = mex{ c[w] : w adj v, committed(w) or offset(w) < offset(v) }
     A full round computes it in lockstep offset order, the paper's own
     schedule (:func:`lockstep_wavefront`): in step k every thread colors
     the k-th vertex of its block, reading only classes already final, so
     ``bs`` steps over the class's own edges reach the fixpoint. Where the
     graph has few edges per static class ``ceil(|V|/P)``
     (``concurrency=1`` is serial greedy, |V| classes of one vertex), and
     on the frontier slab, chaotic sweeps (depth(DAG) of them, the shared
     :func:`repro.core.engine.fixpoint_sweep`) reach the same fixpoint;
  3. conflict detection (Alg. 2 lines 11-14): monochromatic pending pairs
     (necessarily same-offset) queue the higher-index endpoint for the next
     round (:func:`repro.core.engine.speculation_conflicts`).

Limits: ``concurrency=1`` degenerates to serial greedy (0 conflicts,
colors == Alg. 1); ``concurrency >= |V|`` is the fully-concurrent limit (the
XMT's 16K-thread regime). Conflicts grow with ``concurrency`` — the paper's
Fig. 10(a) trend — and the pending set strictly shrinks every round (the
minimum-index vertex of each conflict cluster always survives), so the loop
terminates.

The first-fit inner loop is pluggable (``engine=``): ``"sort"`` (segmented
sort mex), ``"bitmap"`` (O(E) scatter-or forbidden bitmap) or
``"ell_pallas"`` (the Pallas kernel over the graph's ELL layout) — see
engine.py for the registry.

The round loop is two-phase (repro.core.frontier): round 0 takes the full
edge list; rounds >= 1 compact the pending tail and its incident edges
into a static active-set slab and sweep that instead — O(cap) per sweep
rather than O(E) — spilling back to the full path when the frontier
overflows its bucket. Bit-identical either way.

``sweeps_per_round`` counts phase 1's passes over the round's edge set: a
chaotic sweep is one, a whole wavefront is one (its steps read each
forbidding edge once between them) plus, in a round where a class
overflowed its window (``wavefront_spills`` counts such classes), the
chaotic full-edge sweeps that finish the round.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .engine import (EngineSpec, MexFn, SlabMexFn, SweepSpec,
                     fixpoint_sweep, lockstep_offsets, speculation_conflicts)
from .frontier import (compact_frontier, frontier_conflicts, frontier_counts,
                       frontier_sweep)
from .graph import DeviceGraph, pad_bucket
from .mex import segment_mex

# A full round's phase 1 runs as a lockstep wavefront while the envelope
# holds at least this many directed edges per static offset class
# ceil(|V|/P). A step costs a fixed handful of small ops (a window slice, a
# cap_e gather, the slab mex over P rows, a P-row scatter) besides its
# class's edges, which the steps read once between them; a chaotic sweep
# reads the whole edge list, depth+1 times a round. On a TPU v5e (RMAT-G,
# 2^20 vertices, sort engine) a step's fixed cost was about 14 us, what a
# chaotic sweep pays for about 580 edges (0.40 s for 16.8M), so at 64
# edges a class the steps' fixed cost stays under ten full sweeps. That
# bound holds in round 0, where every class has a step and chaotic
# iteration took 22 to 36 sweeps in every scale-20 graph measured (at 256
# to 16,384 classes: the wavefront 1.80 to 2.02 s, chaos 14.6 to 15.0 s);
# later rounds have fewer steps. Below it (``concurrency=1``: |V| classes
# of one vertex) the steps' fixed cost outgrows the sweeps they replace.
WAVEFRONT_EDGES_PER_CLASS = 64


@dataclasses.dataclass
class ColoringResult:
    colors: jnp.ndarray               # [V] int32, >= 1
    rounds: int                       # outer iterations (paper Fig. 10b)
    conflicts_per_round: jnp.ndarray  # [max_rounds] int32 (paper Fig. 10c)
    sweeps_per_round: jnp.ndarray     # [max_rounds] int32 inner sweeps

    # summaries are memoized: results get re-summarized in benchmark and
    # assertion loops, and colors.max() over a large coloring is not free
    @functools.cached_property
    def total_conflicts(self) -> int:
        return int(self.conflicts_per_round.sum())

    @functools.cached_property
    def sweeps(self) -> int:
        """Total inner dataflow sweeps across all rounds."""
        return int(self.sweeps_per_round.sum())

    @functools.cached_property
    def num_colors(self) -> int:
        from .metrics import num_colors as _distinct
        return _distinct(self.colors)


def wavefront_capacities(num_vertices: int, padded_edges: int,
                         max_degree: int, *, concurrency: int,
                         max_sweeps: int) -> Tuple[int, int]:
    """Static ``(cap_v, cap_e)`` of the lockstep wavefront, or ``(0, 0)``
    where full rounds keep the chaotic sweep.

    Every round's block size ``bs = ceil(|pending|/P)`` is at most the
    static class count ``ceil(|V|/P)``. The wavefront engages when the
    envelope holds at least :data:`WAVEFRONT_EDGES_PER_CLASS` edges a
    class and the count is at most ``max_sweeps`` (the chaotic sweep, cut
    at ``max_sweeps``, reaches the fixpoint only then, so the two agree).
    A step holds one vertex per thread, so ``cap_v`` is ``P`` (at most
    |V|) up the :func:`repro.core.graph.pad_bucket` ladder. Class k holds
    at most ``P`` sources' forbidding edges, ``E/classes`` on average over
    its sources' full degrees (in round 0 a source at offset k is
    forbidden by the ``k/bs`` share of its pending neighbours, so the top
    classes come close to it); ``cap_e`` is 5/4 of that, never below two
    max-degree rows. Only the envelope and the spec decide."""
    V, E, P = int(num_vertices), int(padded_edges), int(concurrency)
    if V <= 0 or E <= 0 or P <= 0:
        return 0, 0
    classes = -(-V // P)
    cap_v = pad_bucket(min(P, V), min_bucket=8)
    if (classes * WAVEFRONT_EDGES_PER_CLASS > E or classes > int(max_sweeps)
            # the int32 sort key, whose hub segments (a binned layout)
            # follow the classes
            or (2 * classes + 1) * cap_v >= 2 ** 31):
        return 0, 0
    cap_e = max(-(-5 * E // (4 * classes)), 2 * max(0, int(max_degree)))
    return cap_v, min(E, pad_bucket(cap_e, min_bucket=8))


def hub_windows(cap_e: int) -> Tuple[int, ...]:
    """The static sizes a wavefront step's hub window takes, smallest
    first: quarters down from ``cap_e``, so a step sorts at most four
    times its class's hub edges (or the smallest size), not ``cap_e``."""
    return tuple(sorted({min(cap_e, max(8, cap_e >> s))
                         for s in (6, 4, 2, 0)}))


def lockstep_wavefront(mex: MexFn, mex_slab: SlabMexFn, *,
                       src: jnp.ndarray, dst: jnp.ndarray,
                       slot: Optional[jnp.ndarray], colors: jnp.ndarray,
                       pending: jnp.ndarray, concurrency: int,
                       cap_v: int, cap_e: int, max_sweeps: int,
                       hub_width: int = 0):
    """Phase 1 of a full round in the paper's own schedule: offset class
    0, then 1, ..., then ``bs-1``, one step each.

    Vertices at one offset never forbid each other, so class k reads only
    classes already final and one step per class reaches the fixpoint of
    the chaotic sweep, bit for bit. The edge list is sorted once, keyed by
    the source's (class, thread): only forbidding edges get a real class,
    the rest sort to the end. Step k takes a static ``cap_e`` window of
    the sorted edges at class k's start (positions beyond the class are
    masked), gives each edge the slab row of its source's thread,
    ``rank // bs`` (a thread has one vertex per offset), runs the engine's
    slab mex once and scatters the class's colours.

    A class with more than ``cap_e`` forbidding edges spills: its window
    sees only the first ``cap_e`` of them. The round then finishes with
    chaotic full-edge sweeps from the steps' colours; the fixpoint over
    the offset DAG is unique and every start reaches it, so the answer is
    the same. Without a spill the sweeps' cap is 0 and none runs, so the
    decision costs no branch: under ``vmap`` (``plan.map``) a ``cond``
    runs both of its branches, a while loop only as long as some graph of
    the batch needs it.

    On a binned layout (``hub_width`` > 0, the edges of hub rows carrying
    the slot ``hub_width``) the hub rows' forbidding edges sort after
    every class, in class order, and step k also takes its class's hub
    edges in a window of their own, sized from :func:`hub_windows` by
    their count, for :func:`repro.core.mex.segment_mex` (``hub=True``); a
    thread's colour is the larger of the two mexes. A class spills when
    either part is over ``cap_e``.

    ``mex`` is the engine bound to the whole graph, ``mex_slab`` bound to
    ``cap_v`` rows (of the slab's width alone where the layout is
    binned); ``slot`` is the ELL slot of each edge (``None`` for
    layout-free engines); ``colors`` holds 0 at every pending vertex;
    ``max_sweeps`` caps the sweeps after a spill. Returns ``(colors,
    passes, spills)``: ``passes`` is 1 (the steps read each forbidding
    edge once between them) plus the full sweeps after a spill;
    ``spills`` counts the classes over ``cap_e``."""
    V = pending.shape[0]
    M = src.shape[0]
    num_classes = -(-V // concurrency)
    p32 = pending.astype(jnp.int32)
    r = p32.sum()
    bs = jnp.maximum(lax.div(r + concurrency - 1, concurrency), 1)
    rank = jnp.cumsum(p32) - 1
    # rank within the pending set; -1 for committed vertices and phantom V
    rpad = jnp.concatenate([jnp.where(pending, rank, -1),
                            jnp.full((1,), -1, jnp.int32)])
    rs, rd = rpad[src], rpad[dst]
    # a neighbour forbids src iff committed, or pending at a smaller offset
    forbids = (rs >= 0) & ((rd < 0) | (rd % bs < rs % bs))
    cls, segments = rs % bs, num_classes
    if hub_width:
        # hub rows' edges sort after every class: segment num_classes + k
        cls, segments = (jnp.where(slot == hub_width, cls + num_classes, cls),
                         2 * num_classes)
    key = jnp.where(forbids, cls * cap_v + rs // bs, segments * cap_v)
    ops = lax.sort((key, dst) if slot is None else (key, dst, slot),
                   num_keys=1, is_stable=False)
    skey, sdst = ops[0], ops[1]
    starts = jnp.searchsorted(
        skey, jnp.arange(segments + 1, dtype=jnp.int32) * cap_v
    ).astype(jnp.int32)
    # the pending vertex of each rank; thread t's vertex at offset k has
    # rank t*bs + k
    order = (jnp.full((V,), V, jnp.int32)
             .at[jnp.where(pending, rank, V)]
             .set(jnp.arange(V, dtype=jnp.int32), mode="drop"))
    thread = jnp.arange(cap_v, dtype=jnp.int32)
    win = jnp.arange(cap_e, dtype=jnp.int32)

    def step(k, cpad):
        lo, n = starts[k], starts[k + 1] - starts[k]
        at = thread * bs + k
        # V + 1 lies outside cpad: the scatter drops it
        vert = jnp.where(at < r, order[jnp.minimum(at, V - 1)], V + 1)
        s = jnp.minimum(lo, M - cap_e)
        live = (win >= lo - s) & (win < lo - s + n)
        row = lax.dynamic_slice(skey, (s,), (cap_e,)) - k * cap_v
        wdst = lax.dynamic_slice(sdst, (s,), (cap_e,))
        wslot = (win if slot is None
                 else lax.dynamic_slice(ops[2], (s,), (cap_e,)))
        m = mex_slab(jnp.where(live, row, cap_v), cpad[wdst], wslot)
        if hub_width:
            m = jnp.maximum(m, hub_step(k, cpad))
        return cpad.at[vert].set(m, mode="drop")

    def hub_window(size):
        def run(k, cpad):
            lo = starts[num_classes + k]
            n = starts[num_classes + k + 1] - lo
            s = jnp.minimum(lo, M - size)
            w = jnp.arange(size, dtype=jnp.int32)
            live = (w >= lo - s) & (w < lo - s + n)
            row = (lax.dynamic_slice(skey, (s,), (size,))
                   - (num_classes + k) * cap_v)
            return segment_mex(jnp.where(live, row, cap_v),
                               cpad[lax.dynamic_slice(sdst, (s,), (size,))],
                               cap_v, hub=True)
        return run

    sizes = hub_windows(cap_e)
    windows = [hub_window(z) for z in sizes]

    def hub_step(k, cpad):
        # the smallest window that holds the class's hub edges
        n = starts[num_classes + k + 1] - starts[num_classes + k]
        which = (n > jnp.asarray(sizes[:-1], jnp.int32)).sum()
        m = jnp.ones((cap_v,), jnp.int32)
        for i, run in enumerate(windows):
            # a loop of at most one trip per size, not a switch: under vmap
            # (plan.map) a switch runs every size on every step, a while
            # loop only the sizes some graph of the batch needs
            m = lax.while_loop(
                lambda c, i=i: c[0] == i,
                lambda c, run=run: (jnp.int32(-1), run(k, cpad)),
                (which, m))[1]
        return m

    cpad0 = jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
    cpad = lax.fori_loop(0, bs, step, cpad0)
    spills = (starts[1:] - starts[:-1] > cap_e).sum(dtype=jnp.int32)
    spec = SweepSpec(key_v=jnp.where(forbids, src, V), dyn_idx=dst,
                     dyn=forbids, static_c=jnp.zeros_like(dst))
    colors, n_sweeps, _ = fixpoint_sweep(
        mex, spec, cpad[:V], pending,
        max_sweeps=jnp.where(spills > 0, max_sweeps, 0))
    return colors, 1 + n_sweeps, spills


@functools.partial(
    jax.jit,
    static_argnames=("concurrency", "max_rounds", "max_sweeps", "backend",
                     "color_bound", "frontier_cap_v", "frontier_cap_e",
                     "seed_frontier", "wavefront_cap_e"),
)
def _iterative_impl(g: DeviceGraph, colors0=None, pending0=None, *,
                    concurrency: int, max_rounds: int,
                    max_sweeps: int, backend, color_bound: int = 0,
                    frontier_cap_v: int = 0, frontier_cap_e: int = 0,
                    seed_frontier: bool = False,
                    wavefront_cap_e: int = -1):
    """The speculation round loop. ``colors0``/``pending0`` warm-start it
    from an existing partial coloring (the ``"recolor"`` strategy's
    detect-and-repair entry: committed colors + the conflicted seed set);
    ``None`` is the cold start (no colors, everything pending).
    ``seed_frontier`` lets round 0 take the compacted frontier path — off
    for cold starts (round 0 is all-pending by construction), on for
    seeded repairs, where round 0 IS the tiny conflicted tail.
    ``wavefront_cap_e`` >= 0 overrides :func:`wavefront_capacities`'
    window (0 keeps full rounds on the chaotic sweep; tests only)."""
    V = g.num_vertices
    src, dst = g.src, g.dst
    max_colors = g.max_degree + 1
    if color_bound > 0:
        max_colors = min(max_colors, color_bound)
    mex = backend.bind(num_vertices=V, max_colors=max_colors,
                       ell_slot=g.ell_slot, ell_width=g.ell_width,
                       max_degree=g.max_degree)
    # a binned ELL layout's slab width: rows longer than it (hubs) take
    # the per-edge path (repro.core.mex.segment_mex), 0 where none is binned
    hub = g.hub_width if backend.needs_ell else 0
    # phase 1 of a full round in lockstep offset order where the static
    # class count allows (lockstep_wavefront), else chaotic sweeps
    wave_v, wave_e = wavefront_capacities(
        V, g.padded_edges, g.max_degree, concurrency=concurrency,
        max_sweeps=max_sweeps)
    if wavefront_cap_e >= 0:
        wave_e = min(int(wavefront_cap_e), g.padded_edges) if wave_v else 0
    if wave_e:
        # binned, the steps take hub edges in windows of their own, so the
        # step's slab binds the slab's rows alone
        wave_slab = backend.bind_slab(
            capacity=wave_v,
            max_colors=min(max_colors, hub + 1) if hub else max_colors,
            ell_width=g.ell_width if g.has_ell else g.max_degree,
            max_degree=hub or g.max_degree)
    # frontier execution layer (repro.core.frontier): rounds >= 1 whose
    # pending set fits the static slab run compacted — O(cap) per sweep
    # instead of O(E) — with a bit-identical spill to the full path
    use_frontier = frontier_cap_v > 0 and g.has_frontier
    if use_frontier:
        mex_slab = backend.bind_slab(
            capacity=frontier_cap_v, max_colors=max_colors,
            ell_width=hub or g.max_degree, max_degree=g.max_degree)

    def round_body(state):
        (colors, pending, rnd, conf_hist, sweep_hist, front_hist,
         spills) = state
        # OpenMP-static lockstep offsets over the pending set
        offset = lockstep_offsets(pending, concurrency)
        ppad = jnp.concatenate([pending, jnp.zeros((1,), jnp.bool_)])
        opad = jnp.concatenate(
            [offset, jnp.full((1,), jnp.iinfo(jnp.int32).max, jnp.int32)])

        def full_round(colors):
            # Phase 1 — fixpoint of the offset-precedence dataflow equations.
            if wave_e:
                colors, n_sweeps, n_spills = lockstep_wavefront(
                    mex, wave_slab, src=src, dst=dst, slot=(
                        g.ell_slot if backend.needs_ell else None),
                    colors=jnp.where(pending, 0, colors), pending=pending,
                    concurrency=concurrency, cap_v=wave_v, cap_e=wave_e,
                    max_sweeps=max_sweeps, hub_width=hub)
            else:
                # neighbor forbids src iff committed, or pending at smaller
                # offset
                forbids = ppad[src] & (~ppad[dst] | (opad[dst] < opad[src]))
                spec = SweepSpec(key_v=jnp.where(forbids, src, V),
                                 dyn_idx=dst, dyn=forbids,
                                 static_c=jnp.zeros_like(dst))
                colors, n_sweeps, _ = fixpoint_sweep(
                    mex, spec, jnp.where(pending, 0, colors), pending,
                    max_sweeps=max_sweeps)
                n_spills = jnp.int32(0)

            # Phase 2 — conflicts among same-round pairs; higher index
            # recolors.
            new_pending = speculation_conflicts(src, dst, colors, pending, V)
            return colors, n_sweeps, new_pending, n_spills

        def frontier_round(colors):
            # same equations, compacted: the slab holds every pending vertex
            # and every constraint edge incident to one, so phase 1's
            # fixpoint and phase 2's conflict pass are bit-identical
            slab = compact_frontier(pending, g.inc_ptr, dst,
                                    frontier_cap_v, frontier_cap_e,
                                    hub_width=hub)
            forbid_e = ((slab.src < V)
                        & (~ppad[slab.dst] | (opad[slab.dst] < opad[slab.src])))
            cpad0 = (jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
                     .at[slab.vert].set(0, mode="drop"))
            cpad, n_sweeps, _ = frontier_sweep(
                mex_slab,
                key_v=jnp.where(forbid_e, slab.owner, frontier_cap_v),
                dyn=forbid_e, dyn_idx=slab.dst,
                static_c=jnp.zeros_like(slab.dst), slot=slab.slot,
                write_vert=slab.vert, cpad0=cpad0, max_sweeps=max_sweeps)
            new_pending = frontier_conflicts(slab, cpad, ppad, V)
            return cpad[:V], n_sweeps, new_pending, jnp.int32(0)

        if use_frontier:
            nv, ne = frontier_counts(pending, g.inc_ptr)
            round_ok = jnp.asarray(True) if seed_frontier else (rnd > 0)
            fits = (round_ok & (nv <= frontier_cap_v)
                    & (ne <= frontier_cap_e))
            colors, n_sweeps, new_pending, n_spills = lax.cond(
                fits, frontier_round, full_round, colors)
            front_hist = front_hist.at[rnd].set(jnp.where(fits, nv, 0))
        else:
            colors, n_sweeps, new_pending, n_spills = full_round(colors)

        conf_hist = conf_hist.at[rnd].set(new_pending.sum(dtype=jnp.int32))
        sweep_hist = sweep_hist.at[rnd].set(n_sweeps)
        return (colors, new_pending, rnd + 1, conf_hist, sweep_hist,
                front_hist, spills + n_spills)

    def cond(state):
        pending, rnd = state[1], state[2]
        return jnp.logical_and(jnp.any(pending), rnd < max_rounds)

    init = (
        (jnp.zeros((V,), jnp.int32) if colors0 is None
         else jnp.asarray(colors0, jnp.int32)),
        (jnp.ones((V,), jnp.bool_) if pending0 is None
         else jnp.asarray(pending0, jnp.bool_)),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((max_rounds,), jnp.int32),
        jnp.zeros((max_rounds,), jnp.int32),
        jnp.zeros((max_rounds,), jnp.int32),
        jnp.asarray(0, jnp.int32),
    )
    (colors, pending, rnd, conf_hist, sweep_hist, front_hist,
     spills) = lax.while_loop(cond, round_body, init)
    return (colors, rnd, conf_hist, sweep_hist, front_hist,
            jnp.any(pending), spills)


def color_iterative(
    g,
    concurrency: int = 64,
    max_rounds: int = 64,
    max_sweeps: int = 4096,
    engine: EngineSpec = "sort",
    color_bound: int = 0,
    model: str = "d1",
) -> ColoringResult:
    """Run ITERATIVE with ``concurrency`` lockstep virtual threads.

    ``g`` is a :class:`DeviceGraph` (model="d1" only), or a host
    :class:`repro.core.graph.Graph` / ``BipartiteGraph`` which is lowered
    per ``model``:

    * ``model="d1"``  distance-1 (adjacent vertices differ) — the default;
    * ``model="d2"``  distance-2 (two-hop pairs differ too; Graph input);
    * ``model="pd2"`` bipartite partial distance-2 (BipartiteGraph input;
      colors the left class).

    The speculation/conflict machinery is model-agnostic: richer models are
    purely a different constraint edge space (repro.core.distance2).

    ``engine`` selects the first-fit inner loop by name (``"sort"``,
    ``"bitmap"``, ``"ell_pallas"``) or takes a
    :class:`repro.core.engine.MexBackend` instance directly.
    ``color_bound`` optionally caps the table backends' color capacity
    below the provable Delta+1 bound (a caller-asserted bound — colors at
    or above it lose their forbids silently; see color_distributed).

    Back-compat shim over the registered ``"iterative"``
    :class:`repro.core.api.ColoringStrategy` — same arguments, same
    bit-exact results, legacy :class:`ColoringResult` return. Prefer
    ``repro.core.color(g, strategy="iterative", ...)`` (unified
    :class:`repro.core.api.ColoringReport`, ``ordering=`` support) or
    ``repro.core.compile_plan`` for compile-once reuse."""
    from .api import ColoringSpec, get_strategy  # lazy: api imports us
    spec = ColoringSpec(strategy="iterative", model=model, engine=engine,
                        concurrency=int(concurrency), max_rounds=max_rounds,
                        max_sweeps=max_sweeps, color_bound=int(color_bound))
    raw = get_strategy("iterative").oneshot(spec, g)
    if bool(raw.unconverged):
        raise RuntimeError(f"ITERATIVE did not converge in {max_rounds} rounds")
    return ColoringResult(colors=raw.colors, rounds=int(raw.rounds),
                          conflicts_per_round=raw.conflicts_per_round,
                          sweeps_per_round=raw.sweeps_per_round)
