"""Chip smoke test: drive the coloring system's main path once on a TPU.

Run from the root of a checkout, in one process that owns the chip:

    python chip_smoke.py             # one chip: every single-chip phase
    python chip_smoke.py --chips 4   # the distributed path on a 2x2 mesh

Phases (one chip):

* plan/sort      RMAT-G scale 20 through ``compile_plan`` -> ``plan(g)``
                 twice (iterative strategy, sort engine): valid coloring,
                 palette <= Delta+1, ``plan.traces == 1``;
* kernel         RMAT-ER and RMAT-B scale 20: ``engine="fused_pallas"``
                 against ``engine="sort"`` under the same spec —
                 bit-identical colors, rounds and conflict history — with
                 the Pallas kernel compiled (``tpu_custom_call`` in the
                 plan's HLO, ``interpret`` resolved to False); RMAT-B's
                 hubs are above the slab's width, so its plan is binned
                 and they take the per-edge hub path;
* service        ``AsyncColoringService``: 8 requests from 2 tenants on
                 RMAT-G scale-16 graphs, micro-batched 4 at a time, plus one
                 streamed edge-delta batch; every response validated;
* distributed/1  the distributed strategy on a one-device mesh (the
                 shard_map lowering), RMAT-B scale 18.

``--chips 4`` runs only the distributed strategy on a 2x2 mesh over four
chips: RMAT-B scale 20 under the 1d and 2d partitions, the boundary wire
against the full wire (bit-identical colors, rounds, conflict histories).

Every check prints on its own line. The last line of standard output is
one JSON object ``{"ok": true, "device": {...}}`` naming the device JAX
reports; it is printed only when every phase passed. Without a TPU the
script exits non-zero before doing any work. Run times printed along the
way are set-up information (compilation included), not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

# Graph sizes. Scale 20 (2^20 vertices, ~16M directed edges) is the largest
# the host-side R-MAT generator builds in a few GB of host memory.
PLAN_SCALE = 20
KERNEL_SCALE = 20
SERVICE_SCALE = 16
DIST1_SCALE = 18
DIST4_SCALE = 20

# ITERATIVE's lockstep virtual threads: 2^14, the paper's XMT regime
# (128 processors x 128 streams), so each round's speculation runs at
# |V|/2^14 = 64 vertices per virtual thread at scale 20.
CONCURRENCY = 1 << 14
MAX_ROUNDS = 256


def check(ok: bool, what: str) -> None:
    """Print one check; a failed check ends the run with no result."""
    if not ok:
        print(f"  FAIL {what}", flush=True)
        raise SystemExit(f"chip_smoke: check failed: {what}")
    print(f"  ok   {what}", flush=True)


def require_tpu(count: int):
    """The devices to run on; exits non-zero unless JAX sees ``count`` TPU
    chips (never carries on on the CPU)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: need {count} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices[:count]


def rmat(name: str, scale: int, seed: int = 0):
    from repro.core import rmat as gen
    t0 = time.perf_counter()
    g = gen.paper_graph(name, scale=scale, seed=seed)
    print(f"  {name} scale {scale}: V={g.num_vertices} "
          f"directed E={g.num_directed_edges} max degree={g.max_degree()} "
          f"(built in {time.perf_counter() - t0:.1f}s on the host)",
          flush=True)
    return g


def same_report(a, b) -> bool:
    return (np.array_equal(a.colors, b.colors) and a.rounds == b.rounds
            and np.array_equal(a.conflicts_per_round, b.conflicts_per_round))


def phase_plan_sort() -> None:
    from repro.core import (ColoringSpec, compile_plan, greedy_color,
                            validate_coloring)
    g = rmat("RMAT-G", PLAN_SCALE)
    spec = ColoringSpec(strategy="iterative", engine="sort",
                        concurrency=CONCURRENCY, max_rounds=MAX_ROUNDS)
    plan = compile_plan(spec, g)
    first = plan(g)
    rep = plan(g)
    serial = int(greedy_color(g).max())
    print(f"  colors={rep.num_colors} (serial greedy {serial}) "
          f"rounds={rep.rounds} sweeps={rep.sweeps} "
          f"conflicts={rep.total_conflicts}; first call "
          f"{first.wall_time_s:.1f}s (compile included), second "
          f"{rep.wall_time_s:.2f}s", flush=True)
    check(validate_coloring(g, rep.colors), "valid coloring")
    check(rep.num_colors <= g.max_degree() + 1,
          f"palette {rep.num_colors} <= max degree + 1 = "
          f"{g.max_degree() + 1}")
    check(same_report(first, rep), "second call reproduces the first")
    check(plan.traces == 1, f"plan.traces == 1 (got {plan.traces})")


def phase_kernel() -> None:
    from repro.core import ColoringSpec, compile_plan, validate_coloring
    for family in ("RMAT-ER", "RMAT-B"):
        g = rmat(family, KERNEL_SCALE)
        spec = ColoringSpec(strategy="iterative", engine="sort",
                            concurrency=CONCURRENCY, max_rounds=MAX_ROUNDS)
        ref = compile_plan(spec, g)(g)
        fused = compile_plan(dataclasses.replace(spec,
                                                 engine="fused_pallas"), g)
        width = fused.backend.layout_width(g.max_degree())
        print(f"  fused_pallas: ELL width {width} (max degree "
              f"{g.max_degree()}) interpret={fused.backend.interpret}",
              flush=True)
        check(fused.backend.interpret is False,
              "kernel resolved to compiled mode (interpret=False)")
        hlo = fused.lower(g).compile().as_text()
        check("tpu_custom_call" in hlo, "tpu_custom_call in the plan's HLO")
        rep = fused(g)
        print(f"  sort: colors={ref.num_colors} rounds={ref.rounds} "
              f"conflicts={ref.conflicts_per_round.tolist()}; fused_pallas: "
              f"colors={rep.num_colors} rounds={rep.rounds} hub rows="
              f"{rep.hub_rows} ({rep.wall_time_s:.1f}s, compile included)",
              flush=True)
        check(validate_coloring(g, rep.colors),
              f"{family} fused_pallas coloring valid")
        check(same_report(rep, ref), f"{family} fused_pallas == sort: "
              "colors, rounds, conflict history")
        if width < g.max_degree():
            check(rep.hub_rows > 0 and "hub_mex" in hlo,
                  f"{family}: rows above width {width} took the hub path")


def phase_service() -> None:
    from repro.core import ColoringSpec, validate_coloring
    from repro.serve import AsyncColoringService
    from repro.core import rmat as gen
    graphs = [gen.paper_graph("RMAT-G", scale=SERVICE_SCALE, seed=s)
              for s in range(8)]
    spec = ColoringSpec(strategy="iterative", engine="sort",
                        concurrency=1024, max_rounds=MAX_ROUNDS)
    svc = AsyncColoringService(default_spec=spec, max_batch=4)
    t0 = time.perf_counter()
    handles = [svc.submit(g, tenant=f"t{i % 2}")
               for i, g in enumerate(graphs)]
    svc.drain()
    served = [h.result() for h in handles]
    print(f"  served {len(served)} requests from "
          f"{len(svc.tenant_served)} tenants in "
          f"{time.perf_counter() - t0:.1f}s (compile included); flushes "
          f"{svc.metrics.snapshot()['cumulative']['flush_reasons']}",
          flush=True)
    check(all(validate_coloring(g, s.report.colors)
              for g, s in zip(graphs, served)),
          "every response is a valid coloring")
    check(len(served) == 8, "8 responses")

    rng = np.random.default_rng(0)
    g = graphs[0]
    svc.open_stream("stream", g, ColoringSpec(
        strategy="recolor", engine="sort", concurrency=1024))
    m = g.num_edges // 100
    cur = g.undirected_edges()
    h = svc.submit_delta(
        "stream",
        inserts=np.stack([rng.integers(0, g.num_vertices, m),
                          rng.integers(0, g.num_vertices, m)], 1),
        deletes=cur[rng.integers(0, cur.shape[0], m)])
    svc.drain()
    dr = h.result().result
    dyn = svc.stream("stream")
    print(f"  stream delta: +{dr.inserted}/-{dr.deleted} edges, "
          f"seed={dr.seed_size}, repaired={dr.repaired}", flush=True)
    check(validate_coloring(dyn.graph, dyn.colors),
          "streamed delta leaves a valid coloring")


def phase_distributed_one(devices) -> None:
    from jax.sharding import Mesh
    from repro.core import ColoringSpec, compile_plan, validate_coloring
    g = rmat("RMAT-B", DIST1_SCALE)
    mesh = Mesh(np.asarray(devices[:1]), ("x",))
    plan = compile_plan(ColoringSpec(strategy="distributed", mesh=mesh,
                                     max_rounds=MAX_ROUNDS), g)
    rep = plan(g)
    print(f"  colors={rep.num_colors} rounds={rep.rounds} "
          f"({rep.wall_time_s:.1f}s, compile included)", flush=True)
    check(validate_coloring(g, rep.colors),
          "distributed (1-device mesh) coloring valid")


def phase_distributed_four(devices) -> None:
    from jax.sharding import Mesh
    from repro.core import ColoringSpec, compile_plan, validate_coloring
    g = rmat("RMAT-B", DIST4_SCALE)
    mesh = Mesh(np.asarray(devices).reshape(2, 2), ("data", "model"))
    for partition in ("1d", "2d"):
        reps = {}
        for wire in ("boundary", "full"):
            plan = compile_plan(ColoringSpec(
                strategy="distributed", mesh=mesh, partition=partition,
                wire=wire, max_rounds=MAX_ROUNDS), g)
            reps[wire] = rep = plan(g)
            print(f"  {partition}/{wire}: colors={rep.num_colors} "
                  f"rounds={rep.rounds} conflicts="
                  f"{rep.conflicts_per_round.tolist()} "
                  f"({rep.wall_time_s:.1f}s, compile included)", flush=True)
            check(validate_coloring(g, rep.colors),
                  f"{partition}/{wire} coloring valid")
        check(same_report(reps["boundary"], reps["full"]),
              f"{partition}: boundary wire == full wire (colors, rounds, "
              "conflict history)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the "
                         "distributed path on a 2x2 mesh")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    import jax
    print(f"device: {devices[0].device_kind} x {len(devices)}; compile "
          f"cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phases = [("distributed/4", lambda: phase_distributed_four(devices))]
    else:
        phases = [("plan/sort", phase_plan_sort),
                  ("kernel", phase_kernel),
                  ("service", phase_service),
                  ("distributed/1",
                   lambda: phase_distributed_one(devices))]
    for name, run in phases:
        t0 = time.perf_counter()
        print(f"[{name}]", flush=True)
        run()
        print(f"[{name}] passed in {time.perf_counter() - t0:.1f}s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
