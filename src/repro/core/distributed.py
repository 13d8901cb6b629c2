"""Distributed BSP coloring via shard_map — the Bozdag et al. [6] framework
(the paper's ITERATIVE ancestor) mapped onto a JAX device mesh.

Vertices are partitioned across all mesh devices (:func:`partition_graph`:
1D blocks, or 2D block-cyclic for skewed R-MAT degree distributions), and
every local vertex is classified at partition time as **interior** (no
cross-shard edge — its color never leaves the shard) or **boundary**. Each
BSP round:

  1. local speculative greedy over the device's pending vertices, against
     last round's exchanged snapshot. With local concurrency ``C=1``
     (default) each device colors its pending set *sequentially* — exactly
     the distributed-memory algorithm — realized as the chaotic fixpoint of
     the local offset-precedence dataflow equations via the shared
     :func:`repro.core.engine.fixpoint_sweep` (converges in local-DAG-depth
     sweeps, no communication inside); cross-device pending neighbors are
     speculated against (not forbidden). The first-fit inner loop is the
     pluggable mex backend (``engine=``), bound to the local vertex slab;
  2. the wire — a three-tier exchange of ``(color, pending)`` state, each
     tier bit-identical to the others (DESIGN.md §Distributed):

     * **boundary wire** (the default): only *boundary* colors + pending
       flags cross the wire, bit-packed into int32 words
       (:mod:`repro.parallel.compression`) and scattered through the static
       boundary->halo index map; the shard's own ``[Vl]`` snapshot slice is
       patched locally with no collective at all. Exact because every
       cross-shard read (phase-1 forbids and the conflict pass) targets
       either a local vertex or a remote *boundary* vertex — by definition;
     * **frontier-halo wire** (H-C3, layered on top): when a psum vote says
       every device's pending set fits its frontier slab, the exchange
       shrinks further, to the ``(gid, color)`` pairs of the per-device
       frontier slabs;
     * **full gather** (the spill path, ``wire="full"``): the legacy H-C1
       ``[Vp]`` packed-int16 gather — retained for plan envelopes whose
       halo capacity a served graph overflows, and as the parity oracle;
  3. conflict detection against the exchanged view: monochromatic
     same-round pairs — with C=1 these are exclusively *boundary*
     (cross-device) conflicts, as in [6]; the higher global index recolors;
  4. ``psum`` termination vote.

The whole multi-round algorithm is one ``lax.while_loop`` inside shard_map,
so it lowers/compiles as a single XLA program on the production meshes —
`launch/dryrun.py` exercises it via the rmat_coloring config, and the
``dist_scale`` benchmark family measures bytes-on-wire vs. shard count.
"""
from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.compression import halo_words, pack_halo, unpack_halo

from .engine import (EngineSpec, SweepSpec, edge_slots, fixpoint_sweep,
                     get_backend, lockstep_offsets)
from .frontier import (FrontierSlab, compact_frontier, frontier_counts,
                       frontier_sweep)
from .graph import Graph, ShardLayout

PARTITION_SCHEMES = ("1d", "2d")
WIRES = ("boundary", "full")


def slab_entry_bytes(verts_global: int, wire_colors: int) -> int:
    """Wire bytes one H-C3 frontier-slab entry costs: 4 when the
    ``(gid, color)`` pair packs into a single int32 word (gid needs
    ``bit_length(Vp)`` bits — ``Vp`` doubles as the drop sentinel — plus
    ``bit_length(wire_colors)`` for the color), else 8 via the two-gather
    path. The single packing rule shared by ``_bsp_local``'s trace-time
    decision, the ``dist_scale`` benchmark's accounting, and (re-derived
    independently) the SPMD verifier's WIRE cost model."""
    packed = (wire_colors > 0 and
              int(verts_global).bit_length()
              + int(wire_colors).bit_length() <= 32)
    return 4 if packed else 8


def _grid_shape(num_devices: int):
    """The ``Pr x Pc`` device grid of the 2D block-cyclic scheme: ``Pr`` the
    largest divisor of D at most ``sqrt(D)`` (a prime D degenerates to a
    1 x D grid, i.e. plain cyclic distribution)."""
    Pr = max(1, int(np.sqrt(num_devices)))
    while num_devices % Pr:
        Pr -= 1
    return Pr, num_devices // Pr


def partition_graph(graph: Graph, num_devices: int,
                    pad_edges_to: int = 0, *, scheme: str = "1d",
                    pad_boundary_to: int = 0) -> ShardLayout:
    """Host-side partitioning into the shard-local CSR + halo layout
    (:class:`repro.core.graph.ShardLayout`).

    Device d owns partition-space vertices [d*Vl, (d+1)*Vl); ``lsrc`` holds
    *local* ids (pad = Vl), ``ldst`` *global* ids (pad = Vl*D). Edges stay
    row-contiguous per device (src order), so local ELL slots are
    recoverable on device via :func:`repro.core.engine.edge_slots`.

    Every local vertex is classified: **boundary** iff it has any
    cross-shard edge (as src or dst — symmetric directed edge lists make
    these the same set), else **interior**. ``layout.bnd [D, Bl]`` is the
    static boundary->halo index map the boundary-only wire exchanges
    through; interior vertices never appear in it, nor in any other shard's
    ``ldst``, so their colors structurally cannot leave the shard.

    ``scheme`` picks vertex ownership: ``"1d"`` contiguous blocks of the
    original ids, or ``"2d"`` block-cyclic over a ``Pr x Pc`` device grid
    (ScaLAPACK-style: ``owner(v) = (v mod Pr)*Pc + (v div Pr) mod Pc``,
    local index ``v div D``). R-MAT generators concentrate high-degree
    vertices at low ids, so 1D blocks hand one shard both the widest edge
    slab and the densest boundary; the 2D map spreads each hub region
    across the whole grid, re-balancing El and Bl (the ``dist_scale``
    benchmark family measures both). A ``"2d"`` layout carries the
    original->partition ``perm``; colors come back through
    :meth:`ShardLayout.unpermute`. (This is vertex-grid distribution, not
    Bogle-Slota 2D *edge* partitioning — the local solve keeps every edge
    on its src's owner, so no row/column sub-collectives are needed.)

    ``pad_edges_to`` / ``pad_boundary_to`` pin the slab widths El / Bl to
    fixed capacities (the :class:`repro.core.api.ColoringPlan` path, where
    every served graph must produce identically-shaped slabs); a graph
    whose densest shard exceeds either is rejected rather than truncated.
    """
    if scheme not in PARTITION_SCHEMES:
        raise ValueError(f"unknown partition scheme {scheme!r}; choose "
                         f"from {PARTITION_SCHEMES}")
    D = num_devices
    V = graph.num_vertices
    Vl = -(-V // D)
    Vp = Vl * D
    src, dst = graph.directed_edges()  # src sorted
    perm = None
    if scheme == "2d":
        Pr, Pc = _grid_shape(D)
        ids = np.arange(V, dtype=np.int64)
        owner_of = (ids % Pr) * Pc + (ids // Pr) % Pc
        perm = (owner_of * Vl + ids // D).astype(np.int32)
        src, dst = perm[src], perm[dst]
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
    owner = src // Vl
    counts = np.bincount(owner, minlength=D)
    El = max(1, int(counts.max()))
    if pad_edges_to:
        if El > pad_edges_to:
            raise ValueError(
                f"densest partition holds {El} directed edges, above the "
                f"requested slab capacity pad_edges_to={pad_edges_to}")
        El = int(pad_edges_to)
    lsrc = np.full((D, El), Vl, np.int32)
    ldst = np.full((D, El), Vp, np.int32)
    offsets = np.zeros(D + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    for d in range(D):
        sl = slice(offsets[d], offsets[d + 1])
        k = offsets[d + 1] - offsets[d]
        lsrc[d, :k] = src[sl] - d * Vl
        ldst[d, :k] = dst[sl]
    # interior/boundary split: every endpoint of a cross-shard edge is
    # boundary. Marking dst as well as src keeps the classification exact
    # (= "some remote shard reads this vertex") even for an asymmetric
    # directed edge list; for the symmetric lists Graph produces the two
    # marks coincide.
    cross = owner != (dst // Vl)
    bmask = np.zeros(Vp, np.bool_)
    bmask[src[cross]] = True
    bmask[dst[cross]] = True
    bids = np.flatnonzero(bmask)
    bowner = bids // Vl
    bcounts = np.bincount(bowner, minlength=D)
    Bl = int(bcounts.max()) if bids.size else 0
    if pad_boundary_to:
        if Bl > pad_boundary_to:
            raise ValueError(
                f"densest shard holds {Bl} boundary vertices, above the "
                f"requested halo capacity pad_boundary_to={pad_boundary_to}")
        Bl = int(pad_boundary_to)
    bnd = np.full((D, Bl), Vl, np.int32)
    boffsets = np.zeros(D + 1, np.int64)
    np.cumsum(bcounts, out=boffsets[1:])
    rank = np.arange(bids.size, dtype=np.int64) - boffsets[bowner]
    bnd[bowner, rank] = (bids - bowner * Vl).astype(np.int32)
    return ShardLayout(lsrc=lsrc, ldst=ldst, bnd=bnd, verts_local=Vl,
                       num_vertices=V, num_devices=D, scheme=scheme,
                       perm=perm, boundary_counts=bcounts.astype(np.int64))


def _bsp_local(lsrc, ldst, bnd, *, axis_names: Tuple[str, ...],
               verts_local: int, num_devices: int, local_concurrency: int,
               max_rounds: int, max_sweeps: int, backend, max_colors: int,
               ell_width: int, frontier_cap_v: int = 0,
               frontier_cap_e: int = 0, wire: str = "boundary",
               wire_colors: int = 0):
    """Per-device body (runs under shard_map).

    The wire (DESIGN.md §Distributed / §Perf): the default **boundary
    wire** packs each shard's boundary ``(color, pending)`` entries into
    int32 words (``repro.parallel.compression.pack_halo``; entry width =
    ``bit_length(wire_colors) + 1`` bits, ``wire_colors`` the provable
    Delta+1 color bound) and all-gathers only those — the static
    boundary->halo id map ``bnd`` is gathered ONCE outside the round loop.
    The gathered payload patches the carried ``[Vp]`` snapshot/pending view
    at the (static) boundary ids; the shard's own ``[Vl]`` slice is patched
    locally with no collective. Exact for both the phase-1 forbids and the
    conflict pass: every cross-shard read lands on a remote *boundary*
    vertex by definition, and every local read on the locally-patched
    slice — so colors, rounds and conflict histories are bit-identical to
    the full gather. With ``wire="full"`` (the spill path) each round
    instead gathers the whole packed-int16 ``[Vp]`` vector (H-C1:
    ``color << 1 | pending``, colors below 2^14; one gather serves phase 1
    AND conflict detection, §Perf H-C2).

    Frontier rounds (§Frontier, ``frontier_cap_v > 0``): each device
    compacts its pending vertices + incident slab edges and solves over the
    compacted slab; when EVERY device's pending set fits its vertex slab
    (one psum vote), the wire shrinks further — to a (global id, color)
    gather of the per-device frontier slabs (H-C3), layered on top of the
    boundary tier: it patches the same carried snapshot the boundary wire
    maintains. Any overflow falls back to the full sweep / the configured
    round wire, so results are bit-identical in all regimes. Round 0
    always takes the configured round wire.

    The conflict pass stays fused with the wire decode rather than routing
    through engine.speculation_conflicts — the per-machine specialization
    this driver exists for.
    """
    Vl = verts_local
    Vp = Vl * num_devices
    C = local_concurrency
    lsrc = lsrc.reshape(-1)
    ldst = ldst.reshape(-1)
    bnd = bnd.reshape(-1)
    if wire not in WIRES:
        raise ValueError(f"unknown wire {wire!r}; choose from {WIRES}")
    use_boundary = wire == "boundary"
    Bl = int(bnd.shape[0])
    if use_boundary and Bl > 0 and wire_colors <= 0:
        raise ValueError("wire='boundary' needs wire_colors (the provable "
                         "Delta+1 color bound) to size the packed payload")
    didx = lax.axis_index(axis_names).astype(jnp.int32)
    base = didx * Vl
    gsrc = jnp.where(lsrc < Vl, lsrc + base, Vp)
    dst_local = (ldst >= base) & (ldst < base + Vl)
    dst_loc = jnp.where(dst_local, ldst - base, Vl)  # local id or pad
    lsrc_safe = jnp.minimum(lsrc, Vl)
    slots = edge_slots(lsrc, Vl) if backend.needs_ell else None
    # ell_width IS the true max degree here (color_distributed wires it so);
    # pass it as max_degree too, so a color_bound cap can't mask truncation
    mex = backend.bind(num_vertices=Vl, max_colors=max_colors,
                       ell_slot=slots, ell_width=ell_width,
                       max_degree=ell_width if backend.needs_ell else -1)
    use_frontier = frontier_cap_v > 0
    if use_frontier:
        mex_slab = backend.bind_slab(
            capacity=frontier_cap_v, max_colors=max_colors,
            ell_width=ell_width,
            max_degree=ell_width if backend.needs_ell else -1)
        # per-shard incident-edge pointers, recovered on device from the
        # row-contiguous slab (partition_graph keeps global src order)
        ldeg = (jnp.zeros((Vl + 1,), jnp.int32)
                .at[lsrc_safe].add(1))[:Vl]
        lrow_ptr = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(ldeg)])

    def gather(x):
        return lax.all_gather(x, axis_names, tiled=True)

    def pv(x):
        # mark as device-varying so while_loop carries and cond branches
        # type-check under shard_map's varying-manual-axes tracking
        return lax.pcast(x, axis_names, to="varying")

    if use_boundary and Bl > 0:
        Wb = halo_words(Bl, wire_colors)
        bnd_safe = jnp.minimum(bnd, Vl)
        # the boundary->halo scatter map is static per shard, so ONE gather
        # outside the round loop builds the global id map — zero per-round
        # id traffic; pad rows carry the Vp drop sentinel
        bnd_gids = gather(jnp.where(bnd < Vl, bnd + base, Vp))  # [D*Bl]

    def round_body(state):
        (colors, pending, snap, rnd, conf_hist, sweep_hist,
         front_hist, _) = state
        # (1) last round's snapshot view. ALL nonzero colors forbid —
        # including stale colors of re-pending vertices: over-forbidding
        # never breaks validity (it slightly biases re-colored vertices away
        # from the contested color, which helps) and it lets one exchange
        # per round serve both phase 1 and conflict detection (§Perf H-C2).
        snap_pad = jnp.concatenate([snap, jnp.zeros((1,), jnp.int32)])
        ppad = jnp.concatenate([pending, jnp.zeros((1,), jnp.bool_)])

        # local lockstep offsets (C virtual threads per device)
        offset = lockstep_offsets(pending, C)
        opad = jnp.concatenate([offset, jnp.full((1,), jnp.iinfo(jnp.int32).max, jnp.int32)])

        src_pending = ppad[lsrc_safe] & (lsrc < Vl)

        if use_frontier:
            nv, ne = frontier_counts(pending, lrow_ptr)
            fits_solve = ((rnd > 0) & (nv <= frontier_cap_v)
                          & (ne <= frontier_cap_e))
            # the slab wire only needs the pending VERTICES to fit; every
            # device must fit for the gathered slabs to reconstruct the
            # exact global pending set
            fits_wire = (rnd > 0) & (nv <= frontier_cap_v)
            all_fit = lax.psum(
                1 - fits_wire.astype(jnp.int32), axis_names) == 0

            # one compaction serves the local solve, the wire and the
            # conflict pass — built only when some branch will consume it
            # (spilled rounds, incl. round 0, skip the work entirely); slab
            # row space is LOCAL vertex ids, edge targets stay GLOBAL (the
            # ldst id space, pad = Vp)
            def _compact(_):
                return compact_frontier(pending, lrow_ptr, ldst,
                                        frontier_cap_v, frontier_cap_e,
                                        dst_pad=Vp)

            def _empty_slab(_):
                # constant fills are device-invariant; the compacting
                # branch's slabs vary per device, so both must be varying
                return FrontierSlab(
                    vert=pv(jnp.full((frontier_cap_v,), Vl, jnp.int32)),
                    owner=pv(jnp.full((frontier_cap_e,), frontier_cap_v,
                                      jnp.int32)),
                    src=pv(jnp.full((frontier_cap_e,), Vl, jnp.int32)),
                    dst=pv(jnp.full((frontier_cap_e,), Vp, jnp.int32)),
                    slot=pv(jnp.zeros((frontier_cap_e,), jnp.int32)),
                    nv=nv, ne=ne)

            slab = lax.cond(fits_solve | all_fit, _compact, _empty_slab, 0)

        # (2) local sequential greedy as an offset-DAG fixpoint (no comms):
        # preceding local-pending neighbors track the live local colors,
        # everyone else contributes the frozen global snapshot.
        def full_solve(colors):
            nbr_local_pending = ppad[dst_loc]  # local *and* pending
            precede = nbr_local_pending & (opad[dst_loc] < opad[lsrc_safe])
            spec = SweepSpec(key_v=jnp.where(src_pending, lsrc, Vl),
                             dyn_idx=dst_loc, dyn=precede,
                             static_c=snap_pad[ldst])
            colors, n_sweeps, _ = fixpoint_sweep(
                mex, spec, jnp.where(pending, 0, colors), pending,
                max_sweeps=max_sweeps, wrap=pv)
            return colors, n_sweeps

        def slab_solve(colors):
            e_local = (slab.dst >= base) & (slab.dst < base + Vl)
            e_loc = jnp.where(e_local, slab.dst - base, Vl)
            precede = ppad[e_loc] & (opad[e_loc] < opad[jnp.minimum(slab.src, Vl)])
            live = slab.src < Vl
            cpad0 = (jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
                     .at[slab.vert].set(0, mode="drop"))
            cpad, n_sweeps, _ = frontier_sweep(
                mex_slab,
                key_v=jnp.where(live, slab.owner, frontier_cap_v),
                dyn=precede, dyn_idx=e_loc,
                static_c=snap_pad[jnp.minimum(slab.dst, Vp)],
                slot=slab.slot, write_vert=slab.vert, cpad0=cpad0,
                max_sweeps=max_sweeps, wrap=pv)
            return cpad[:Vl], n_sweeps

        if use_frontier:
            colors, n_sweeps = lax.cond(fits_solve, slab_solve, full_solve,
                                        colors)
        else:
            colors, n_sweeps = full_solve(colors)

        # (3) the wire: boundary-packed exchange (default), the full packed
        # gather (spill), or the frontier-halo exchange on top
        def full_wire(colors):
            packed_local = ((colors << 1)
                            | pending.astype(jnp.int32)).astype(jnp.int16)
            packed_glob = gather(packed_local)                  # [Vp] int16
            return (packed_glob.astype(jnp.int32) >> 1,
                    (packed_glob & 1).astype(jnp.bool_))

        def boundary_wire(colors):
            # only boundary (color, pending) entries cross the wire,
            # bit-packed; interior state of remote shards is never read, and
            # the shard's own [Vl] snapshot slice needs no collective at all
            if Bl > 0:
                cpadl = jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
                words = pack_halo(cpadl[bnd_safe], ppad[bnd_safe],
                                  wire_colors)                  # [Wb] int32
                gw = gather(words).reshape(num_devices, Wb)
                gcol, gpend = unpack_halo(gw, Bl, wire_colors)  # [D, Bl]
                snap2 = snap.at[bnd_gids].set(gcol.reshape(-1), mode="drop")
                pend2 = (jnp.zeros((Vp,), jnp.bool_)
                         .at[bnd_gids].set(gpend.reshape(-1), mode="drop"))
            else:
                # no cross-shard edges at all (D=1, or disconnected shards):
                # the local patch below is the whole exchange
                snap2, pend2 = snap, jnp.zeros((Vp,), jnp.bool_)
            snap2 = lax.dynamic_update_slice(snap2, colors, (base,))
            pend2 = lax.dynamic_update_slice(pend2, pending, (base,))
            return snap2, pend2

        # H-C3 slab entries are (gid, color) pairs; when both fields fit one
        # 32-bit word, the slab exchange ships ONE packed int32 gather
        # instead of two (slab_entry_bytes is the shared packing rule).
        # Static decision; at billion-edge Vp the fields outgrow a word and
        # the two-gather path remains. Lossless either way, so the tiers
        # stay bit-identical. wire_colors <= 0 (a caller without a provable
        # color bound, e.g. shape-only dry runs) also keeps two gathers.
        slab_cbits = int(wire_colors).bit_length()
        slab_packed = slab_entry_bytes(Vp, wire_colors) == 4

        def slab_wire(colors):
            # only this round's pending vertices changed color or pending
            # state: gather (gid, color) of the per-device frontier slabs
            # and patch the carried snapshot/pending view
            gids = jnp.where(slab.vert < Vl, slab.vert + base, Vp)
            cols = jnp.concatenate(
                [colors, jnp.zeros((1,), jnp.int32)])[jnp.minimum(slab.vert, Vl)]
            if slab_packed:
                words = ((gids.astype(jnp.uint32) << slab_cbits)
                         | cols.astype(jnp.uint32)).astype(jnp.int32)
                gw = gather(words).astype(jnp.uint32)           # [D*cap_v]
                g_gids = (gw >> slab_cbits).astype(jnp.int32)
                g_cols = (gw & jnp.uint32((1 << slab_cbits) - 1)
                          ).astype(jnp.int32)
            else:
                g_gids = gather(gids)                           # [D*cap_v]
                g_cols = gather(cols)
            snap2 = snap.at[g_gids].set(g_cols, mode="drop")
            pend2 = (jnp.zeros((Vp,), jnp.bool_)
                     .at[g_gids].set(True, mode="drop"))
            return snap2, pend2

        round_wire = boundary_wire if use_boundary else full_wire
        if use_frontier:
            snap, pend_glob = lax.cond(all_fit, slab_wire, round_wire, colors)
        else:
            snap, pend_glob = round_wire(colors)
        cgpad = jnp.concatenate([snap, jnp.zeros((1,), jnp.int32)])
        agpad = jnp.concatenate([pend_glob, jnp.zeros((1,), jnp.bool_)])

        # (4) same-round conflicts (boundary + same-offset); higher gid
        # recolors — over the frontier slab when it holds all local rows
        def full_conf(_):
            conf_e = (src_pending & agpad[ldst]
                      & (cgpad[gsrc] == cgpad[ldst]) & (gsrc > ldst))
            return (jnp.zeros((Vl,), jnp.int32)
                    .at[lsrc].max(conf_e.astype(jnp.int32), mode="drop")
                    .astype(jnp.bool_))

        def slab_conf(_):
            gsrc_e = jnp.where(slab.src < Vl, slab.src + base, Vp)
            conf_e = (agpad[jnp.minimum(slab.dst, Vp)]
                      & (cgpad[jnp.minimum(gsrc_e, Vp)]
                         == cgpad[jnp.minimum(slab.dst, Vp)])
                      & (gsrc_e > slab.dst))
            return (jnp.zeros((Vl,), jnp.int32)
                    .at[slab.src].max(conf_e.astype(jnp.int32), mode="drop")
                    .astype(jnp.bool_))

        if use_frontier:
            new_pending = lax.cond(fits_solve, slab_conf, full_conf, 0)
        else:
            new_pending = full_conf(0)

        # (5) global termination vote
        total = lax.psum(new_pending.sum(dtype=jnp.int32), axis_names)
        conf_hist = conf_hist.at[rnd].set(total)
        # local sweep depth this round; the caller maxes across devices
        sweep_hist = sweep_hist.at[rnd].set(n_sweeps)
        if use_frontier:
            front = lax.psum(jnp.where(fits_wire, nv, 0), axis_names)
            front_hist = front_hist.at[rnd].set(
                jnp.where(all_fit, front, 0))
        return (colors, new_pending, snap, rnd + 1, conf_hist,
                sweep_hist, front_hist, total)

    def cond(state):
        total = state[-1]
        rnd = state[3]
        return jnp.logical_and(total > 0, rnd < max_rounds)

    init = (pv(jnp.zeros((Vl,), jnp.int32)), pv(jnp.ones((Vl,), jnp.bool_)),
            pv(jnp.zeros((Vp,), jnp.int32)),   # snapshot: all uncolored
            pv(jnp.asarray(0, jnp.int32)), pv(jnp.zeros((max_rounds,), jnp.int32)),
            pv(jnp.zeros((max_rounds,), jnp.int32)),
            pv(jnp.zeros((max_rounds,), jnp.int32)),
            jnp.asarray(1, jnp.int32))  # psum output is axis-invariant
    (colors, pending, snap, rnd, conf_hist, sweep_hist,
     front_hist, _) = lax.while_loop(cond, round_body, init)
    return (colors[None], rnd[None], conf_hist[None], sweep_hist[None],
            front_hist[None])


def build_distributed_coloring(mesh: Mesh, verts_local: int, edges_local: int,
                               local_concurrency: int = 1,
                               max_rounds: int = 64, max_sweeps: int = 16384,
                               engine: EngineSpec = "sort",
                               max_colors: int = 0, ell_width: int = 0,
                               frontier_cap_v: int = 0,
                               frontier_cap_e: int = 0,
                               wire: str = "boundary",
                               wire_colors: int = 0):
    """Build the jitted shard_map coloring program for a mesh.

    Returns ``fn(lsrc [D, El], ldst [D, El], bnd [D, Bl]) -> (colors
    [D, Vl], rounds, conflicts_per_round, sweeps_per_round,
    frontier_per_round)``; inputs/outputs sharded over all mesh axes
    (``sweeps_per_round`` is the deepest local fixpoint across devices each
    round; ``frontier_per_round`` the global frontier size when the round
    took the compacted wire, else 0). Static shapes, so the identical
    program serves dry-run lowering.

    ``engine`` picks the local first-fit backend; ``max_colors`` (global
    Delta+1, possibly capped by ``color_bound``) sizes the bitmap/ell
    backends; ``ell_width`` (max degree of any owned vertex) is required
    for the ELL-slab engines (``"ell_pallas"``, ``"fused_pallas"``).
    ``frontier_cap_v``/``frontier_cap_e`` enable the per-shard frontier
    slabs (0 = full sweeps every round; see repro.core.frontier).

    ``wire`` picks the per-round exchange (see :func:`_bsp_local`):
    ``"boundary"`` (default) exchanges only the packed boundary payload —
    the halo slab width Bl is the ``bnd`` operand's second dim
    (``ShardLayout.bnd``; 0 = no cross-shard edges, zero wire bytes) and
    ``wire_colors`` the *uncapped* provable Delta+1 bound sizing the packed
    entries (never the ``color_bound``-capped table capacity: a capped
    table can still assign any color up to Delta+1). ``"full"`` gathers the
    whole [Vp] packed vector every non-frontier round; the ``bnd`` operand
    is still threaded (shapes stay wire-independent) but unused.
    """
    backend = get_backend(engine)
    if backend.needs_ell and ell_width <= 0:
        raise ValueError(f"engine={backend.name!r} needs ell_width (the max "
                         "degree across owned vertices) — color_distributed "
                         "wires it from the host graph automatically")
    if backend.needs_color_bound and max_colors <= 0:
        raise ValueError(
            f"engine={backend.name!r} needs max_colors (global Delta+1) — "
            "color_distributed wires it from the host graph automatically")
    axis_names = tuple(mesh.axis_names)
    D = int(np.prod(mesh.devices.shape))
    body = functools.partial(
        _bsp_local, axis_names=axis_names, verts_local=verts_local,
        num_devices=D, local_concurrency=local_concurrency,
        max_rounds=max_rounds, max_sweeps=max_sweeps, backend=backend,
        max_colors=max_colors, ell_width=ell_width,
        frontier_cap_v=frontier_cap_v, frontier_cap_e=frontier_cap_e,
        wire=wire, wire_colors=wire_colors)
    spec_in = P(axis_names, None)
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_in, spec_in, spec_in),
        out_specs=(P(axis_names, None), P(axis_names), P(axis_names, None),
                   P(axis_names, None), P(axis_names, None)),
        # the Pallas interpreter (the CPU path of the kernel engines)
        # evaluates kernel bodies with untyped constants, which varying-
        # axes checking rejects against device-varying blocks; compiled
        # kernels and every other engine keep the check
        check_vma=not getattr(backend, "interpret", False),
    )

    def run(lsrc, ldst, bnd):
        colors, rnd, conf, sweeps, fronts = smapped(lsrc, ldst, bnd)
        return (colors, rnd.max(), conf.max(axis=0), sweeps.max(axis=0),
                fronts.max(axis=0))

    return jax.jit(run)


def color_distributed(graph, mesh: Mesh, local_concurrency: int = 1,
                      max_rounds: int = 64, engine: EngineSpec = "sort",
                      color_bound: int = 0, model: str = "d1"):
    """End-to-end: partition on host, color on the mesh, return colors [V]
    (``[num_left]`` under ``model="pd2"``).

    Back-compat shim over the registered ``"distributed"``
    :class:`repro.core.api.ColoringStrategy` (which owns the
    partition/build/run sequence); kept for its legacy return shape
    ``(colors, rounds, conflicts_per_round)``. Prefer
    ``repro.core.color(graph, strategy="distributed", mesh=mesh)`` — same
    machinery, richer :class:`repro.core.api.ColoringReport` (sweeps,
    wall time), and ``ordering=`` support.

    ``model`` selects the coloring semantics ("d1" | "d2" | "pd2", the
    latter taking a :class:`repro.core.graph.BipartiteGraph`): the host
    graph is lowered to its constraint graph (repro.core.distance2) and the
    BSP machinery runs on that unchanged. The boundary exchange widens to
    two-hop halos *structurally*: partitioning (and hence the
    interior/boundary split) happens on the *constraint* graph, so a vertex
    two hops away in the input graph is one constraint edge away — already
    in the boundary set if it crosses shards. D2's wider stencil changes
    only which constraint edges exist, never the wire protocol — no new
    collective, no second exchange (DESIGN.md §Models).

    ``color_bound`` optionally caps the table-backend color capacity below
    the provable Delta+1 bound (greedy on the paper's graphs uses <= 143
    colors while Delta reaches 10^4+ on skewed R-MAT, so the provable bound
    wastes Theta(V*Delta) table memory per sweep; under ``model="d2"``
    Delta is the even larger *squared-graph* degree). It is a
    caller-asserted bound: colors at or above it lose their forbids
    silently, so only cap when the chromatic behavior of the graph family
    is known. This is also what makes the dry-run's
    ``ColoringConfig.color_bound`` program reproducible here at runtime."""
    from .api import ColoringSpec, get_strategy  # lazy: api imports us
    spec = ColoringSpec(strategy="distributed", model=model, engine=engine,
                        max_rounds=max_rounds, max_sweeps=16384,
                        color_bound=int(color_bound), mesh=mesh,
                        local_concurrency=local_concurrency)
    raw = get_strategy("distributed").oneshot(spec, graph)
    return (np.asarray(raw.colors), int(raw.rounds),
            np.asarray(raw.conflicts_per_round))
