"""Frontier execution layer tests (repro.core.frontier): bit-parity vs
``frontier="off"`` across the strategy x engine x model matrix, the
spill-to-full fallback on slab overflow, the compaction-completeness
property (no active constraint edge is ever dropped), and the plan
zero-retrace guarantee with the frontier enabled (tier-1's regression pin
for the PR-3 contract)."""
import numpy as np
import pytest

from repro.core import (BipartiteGraph, ColoringSpec, Graph, PlanShape,
                        color, compile_plan, rmat, validate_coloring,
                        validate_d2_coloring, validate_pd2_coloring)
from repro.core.frontier import (compact_frontier, frontier_capacities,
                                 resolve_frontier)
from repro.core.graph import pad_bucket

STRATEGIES = ["iterative", "dataflow"]
ENGINES = ["sort", "bitmap", "ell_pallas"]
MODELS = ["d1", "d2", "pd2"]


def _graph(name="RMAT-G", scale=8, seed=1):
    return rmat.paper_graph(name, scale=scale, seed=seed)


def _bipartite(seed=0, L=120, R=80, m=600):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        L, R, np.stack([rng.integers(0, L, m), rng.integers(0, R, m)], 1))


def _assert_same_report(off, on):
    """Same colors, rounds and conflicts. Phase-1 passes follow each
    path's own rule: DATAFLOW's one round sweeps alike either way; an
    ITERATIVE round on the full path records one lockstep-wavefront pass
    (repro.core.iterative), the same in both runs, and with no spilled
    class nothing more; a compacted round records its chaotic sweeps."""
    np.testing.assert_array_equal(off.colors, on.colors)
    assert off.rounds == on.rounds
    np.testing.assert_array_equal(off.conflicts_per_round,
                                  on.conflicts_per_round)
    if on.spec.strategy == "dataflow":
        np.testing.assert_array_equal(off.sweeps_per_round,
                                      on.sweeps_per_round)
        return
    full = on.frontier_sizes_per_round == 0
    np.testing.assert_array_equal(off.sweeps_per_round[full],
                                  on.sweeps_per_round[full])
    for rep in (off, on):
        f = rep.frontier_sizes_per_round == 0
        if rep.wavefront_spills == 0:
            assert (rep.sweeps_per_round[f] == 1).all()
        assert (rep.sweeps_per_round[f] >= 1).all()
    assert (on.sweeps_per_round[~full] >= 1).all()


# ------------------------------------------------------------- bit parity
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("model", MODELS)
def test_frontier_bit_parity_matrix(strategy, engine, model):
    """THE frontier guarantee: identical colors, rounds, conflict and sweep
    histories with the frontier on vs off, for every strategy x engine x
    model cell (square lowering — the frontier needs row-deduped CSR)."""
    g = _bipartite() if model == "pd2" else _graph(scale=8)
    base = dict(strategy=strategy, model=model, engine=engine,
                lowering="square", concurrency=8, max_rounds=256)
    off = color(g, ColoringSpec(frontier="off", **base))
    on = color(g, ColoringSpec(frontier="on", **base))
    _assert_same_report(off, on)
    valid = {"d1": validate_coloring, "d2": validate_d2_coloring,
             "pd2": validate_pd2_coloring}[model]
    assert valid(g, on.colors)


def test_frontier_engages_and_reports_sizes():
    """With a generous slab every round >= 1 runs compacted, and the report
    exposes the per-round frontier sizes (== the previous round's conflict
    count for ITERATIVE)."""
    g = _graph("RMAT-G", scale=10, seed=0)
    on = color(g, strategy="iterative", concurrency=64, max_rounds=256,
               frontier="on", frontier_capacity=1 << 10)
    off = color(g, strategy="iterative", concurrency=64, max_rounds=256,
                frontier="off")
    _assert_same_report(off, on)
    assert on.rounds > 1, "need a conflicted run to exercise the frontier"
    fs = on.frontier_sizes_per_round
    assert fs[0] == 0                      # round 0 always takes the full path
    np.testing.assert_array_equal(fs[1:], on.conflicts_per_round[:-1])
    assert off.frontier_sizes_per_round.sum() == 0


def test_frontier_dataflow_active_set_sweeps():
    """DATAFLOW's frontier compacts the changed-dependent active set per
    sweep; entry 0 of the frontier history counts the compacted sweeps."""
    g = _graph("RMAT-ER", scale=9, seed=2)
    on = color(g, strategy="dataflow", frontier="on",
               frontier_capacity=1 << 9)
    off = color(g, strategy="dataflow", frontier="off")
    np.testing.assert_array_equal(off.colors, on.colors)
    assert off.sweeps == on.sweeps
    assert int(on.frontier_sizes_per_round[0]) > 0
    assert int(off.frontier_sizes_per_round[0]) == 0


def test_frontier_overflow_spills_to_full_path():
    """A deliberately tiny slab forces the spill: rounds whose pending set
    overflows run the full path (frontier size 0), later rounds that fit
    run compacted — and the result is STILL bit-identical."""
    g = _graph("RMAT-B", scale=9, seed=0)
    off = color(g, strategy="iterative", concurrency=256, max_rounds=256,
                frontier="off")
    on = color(g, strategy="iterative", concurrency=256, max_rounds=256,
               frontier="on", frontier_capacity=8)
    _assert_same_report(off, on)
    fs = on.frontier_sizes_per_round
    conf = np.concatenate([[g.num_vertices], on.conflicts_per_round[:-1]])
    cap_v, cap_e = frontier_capacities(
        g.num_vertices, g.num_directed_edges, g.max_degree(), capacity=8)
    spilled = fs[1:][conf[1:] > cap_v]
    assert spilled.size and (spilled == 0).all(), \
        "overflowing rounds must take the full path"
    assert (fs[1:][fs[1:] > 0] <= cap_v).all()


def test_frontier_off_for_wedge_lowering_auto_and_raises_on():
    """The wedge multiset carries no incident-edge auxiliary: frontier
    'auto' silently runs full sweeps, 'on' refuses loudly."""
    g = _graph(scale=7)
    auto = color(g, model="d2", lowering="wedge", concurrency=8,
                 max_rounds=256)  # frontier defaults to "auto"
    assert auto.frontier_sizes_per_round.sum() == 0
    with pytest.raises(ValueError, match="frontier='on'"):
        color(g, model="d2", lowering="wedge", frontier="on",
              concurrency=8, max_rounds=256)
    with pytest.raises(ValueError, match="unknown frontier mode"):
        ColoringSpec(frontier="maybe")


# ------------------------------------------------------- compaction property
def _compaction_reference(g: Graph, active: np.ndarray):
    src, dst = g.directed_edges()
    keep = active[src]
    return sorted(zip(src[keep].tolist(), dst[keep].tolist()))


def _check_compaction(g: Graph, active: np.ndarray):
    dg = g.to_device()
    deg = np.diff(g.row_ptr)
    nv = int(active.sum())
    ne = int(deg[active].sum())
    cap_v = pad_bucket(max(nv, 1), min_bucket=8)
    cap_e = pad_bucket(max(ne, 1), min_bucket=8)
    slab = compact_frontier(np.asarray(active), dg.inc_ptr, dg.dst,
                            cap_v, cap_e)
    assert int(slab.nv) == nv and int(slab.ne) == ne
    vert = np.asarray(slab.vert)
    src_s, dst_s = np.asarray(slab.src), np.asarray(slab.dst)
    owner = np.asarray(slab.owner)
    live_v = vert < g.num_vertices
    np.testing.assert_array_equal(np.sort(vert[live_v]),
                                  np.flatnonzero(active))
    live_e = src_s < g.num_vertices
    got = sorted(zip(src_s[live_e].tolist(), dst_s[live_e].tolist()))
    assert got == _compaction_reference(g, active), \
        "compaction dropped or invented an active constraint edge"
    # owner/slot consistency: each slab edge sits in its owner's row
    np.testing.assert_array_equal(src_s[live_e], vert[owner[live_e]])


def test_compaction_explicit_cases():
    n = 12
    ring = Graph.from_edges(
        n, np.stack([np.arange(n), (np.arange(n) + 1) % n], 1))
    star = Graph.from_edges(
        n, np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], 1))
    for g in (ring, star):
        for mask in (np.zeros(n, bool),
                     np.ones(n, bool),
                     np.arange(n) % 3 == 0):
            _check_compaction(g, mask)


@pytest.mark.parametrize("n", [1, 2, 9])
def test_compaction_of_edgeless_graph(n):
    """E=0: the slab holds the active vertices and only padding edges —
    compaction must not gather from the empty edge-target array."""
    g = Graph.from_edges(n, np.zeros((0, 2), np.int64))
    assert g.num_directed_edges == 0
    for mask in (np.zeros(n, bool), np.ones(n, bool)):
        _check_compaction(g, mask)
        slab = compact_frontier(np.asarray(mask), g.to_device().inc_ptr,
                                g.to_device().dst, 8, 8)
        assert (np.asarray(slab.dst) == n).all()


def test_compaction_overflow_reports_true_counts():
    """When the active set exceeds the slab, nv/ne still report the TRUE
    counts (the spill signal) and the slab stays well-formed."""
    n = 32
    rng = np.random.default_rng(0)
    g = Graph.from_edges(
        n, np.stack([rng.integers(0, n, 200), rng.integers(0, n, 200)], 1))
    dg = g.to_device()
    active = np.ones(n, bool)
    slab = compact_frontier(np.asarray(active), dg.inc_ptr, dg.dst, 8, 16)
    assert int(slab.nv) == n
    assert int(slab.ne) == g.num_directed_edges
    assert (np.asarray(slab.vert) < n).all()       # first 8 active vertices
    src_s = np.asarray(slab.src)
    dst_s = np.asarray(slab.dst)
    live = src_s < n
    ref = dict()
    gs, gd = g.directed_edges()
    for pair in zip(gs.tolist(), gd.tolist()):
        ref[pair] = ref.get(pair, 0) + 1
    for pair in zip(src_s[live].tolist(), dst_s[live].tolist()):
        assert pair in ref, "overflowed compaction fabricated an edge"


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is in requirements.txt
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def graph_and_mask(draw, max_v=24, max_e=80):
        n = draw(st.integers(2, max_v))
        m = draw(st.integers(0, max_e))
        edges = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        mask = draw(st.lists(st.booleans(), min_size=n, max_size=n))
        g = Graph.from_edges(n, np.array(edges or [[0, 0]], dtype=np.int64))
        return g, np.array(mask, bool)

    @settings(max_examples=40, deadline=None)
    @given(graph_and_mask())
    def test_compaction_never_drops_an_active_edge(gm):
        """Property: the slab edge multiset == every directed constraint
        edge whose src is active, exactly once, whenever the slab fits."""
        g, mask = gm
        _check_compaction(g, mask)


# --------------------------------------------------- plans: zero retrace
def test_frontier_plan_zero_retrace():
    """The PR-3 contract survives the frontier: a frontier-enabled plan
    serves same-bucket graphs with plan.traces pinned at one (capacities
    come from the static envelope, never from data)."""
    gs = [_graph("RMAT-G", scale=8, seed=s) for s in range(3)]
    shape = PlanShape(
        num_vertices=gs[0].num_vertices,
        padded_edges=pad_bucket(max(g.num_directed_edges for g in gs)),
        max_degree=max(g.max_degree() for g in gs))
    for mode in ["auto", "on"]:
        spec = ColoringSpec(strategy="iterative", engine="bitmap",
                            concurrency=64, frontier=mode,
                            frontier_capacity=1 << 10)
        plan = compile_plan(spec, shape)
        reports = [plan(g) for g in gs]
        assert plan.traces == 1, mode
        for g, rep in zip(gs, reports):
            assert validate_coloring(g, rep.colors)
            off = color(g, ColoringSpec(strategy="iterative", engine="bitmap",
                                        concurrency=64, frontier="off"))
            np.testing.assert_array_equal(rep.colors, off.colors)
        assert any(r.frontier_sizes_per_round.sum() > 0 for r in reports), \
            "plan runs never exercised the frontier path"


def test_frontier_distributed_parity_2dev():
    """The BSP driver's per-shard frontier (compacted local solve + the
    shrunken frontier-halo wire) is bit-identical to the full wire across
    a real multi-device mesh, and engages once per-device pending sets fit
    their slabs."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = src
    code = textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import rmat, color, validate_coloring
        mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
        g = rmat.paper_graph("RMAT-B", scale=9, seed=3)
        off = color(g, strategy="distributed", mesh=mesh, max_sweeps=16384,
                    frontier="off")
        on = color(g, strategy="distributed", mesh=mesh, max_sweeps=16384,
                   frontier="on", frontier_capacity=1 << 8)
        print(json.dumps(dict(
            valid=bool(validate_coloring(g, on.colors)),
            same=bool(np.array_equal(off.colors, on.colors)),
            rounds=[int(off.rounds), int(on.rounds)],
            conf_same=bool(np.array_equal(off.conflicts_per_round,
                                          on.conflicts_per_round)),
            sweeps_same=bool(np.array_equal(off.sweeps_per_round,
                                            on.sweeps_per_round)),
            frontier=[int(x) for x in on.frontier_sizes_per_round],
            frontier_off=int(off.frontier_sizes_per_round.sum()))))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["valid"] and r["same"] and r["conf_same"] and r["sweeps_same"]
    assert r["rounds"][0] == r["rounds"][1]
    assert r["frontier_off"] == 0
    assert sum(r["frontier"]) > 0, "distributed frontier never engaged"


def test_resolve_frontier_modes():
    g = _graph(scale=7)
    dg = g.to_device()
    assert resolve_frontier("off", 0, num_vertices=dg.num_vertices,
                            padded_edges=dg.padded_edges,
                            max_degree=dg.max_degree, has_inc=True) == (0, 0)
    cv, ce = resolve_frontier("auto", 0, num_vertices=dg.num_vertices,
                              padded_edges=dg.padded_edges,
                              max_degree=dg.max_degree, has_inc=True)
    assert cv > 0 and ce >= cv
    # capacities ride the pad_bucket ladder (static-shape quantization)
    assert cv == pad_bucket(cv, min_bucket=8)
    assert ce == pad_bucket(ce, min_bucket=8)
    assert resolve_frontier("auto", 0, num_vertices=dg.num_vertices,
                            padded_edges=dg.padded_edges,
                            max_degree=dg.max_degree,
                            has_inc=False) == (0, 0)
