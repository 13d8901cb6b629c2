"""The binned ELL layout (repro.core.engine.HUB_WIDTH): a ``fused_pallas``
plan whose envelope max degree is above the bin keeps the slab and the
``round_fused`` kernel for the rows that fit it and gives longer rows (hubs)
the per-edge path (``segment_mex(..., hub=True)``, its sort named
``hub_mex``). Colors stay bit-identical to the sort engine
and to the serial greedy oracle on RMAT-B, the paper's skewed family, across
the wavefront, the frontier rounds, forced spills, ``plan.map``, a seeded
``recolor`` and DATAFLOW; the counters equal a numpy count; a plan whose
envelope fits the bin builds no hub path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ColoringSpec, PlanShape, compile_plan,
                        greedy_color, rmat, spans, validate_coloring)
from repro.core import engine
from repro.core.engine import get_backend
from repro.core.frontier import resolve_frontier
from repro.core.graph import pad_bucket
from repro.core.iterative import _iterative_impl, hub_windows
from repro.core.mex import segment_mex

# (bin width, scale): the shipped bin on the largest scale the CPU tests
# run, where a few RMAT-B rows pass it, and a narrow bin at scale 10,
# where many do
BINS = [(None, 12), (16, 10)]


@pytest.fixture(params=BINS, ids=lambda b: f"W{b[0] or 'ship'}-s{b[1]}")
def binned(request, monkeypatch):
    """(RMAT-B graph, envelope above the bin, bin width)."""
    width, scale = request.param
    if width is not None:
        monkeypatch.setattr(engine, "HUB_WIDTH", width)
    W = engine.HUB_WIDTH
    g = rmat.paper_graph("RMAT-B", scale=scale, seed=1)
    assert (g.degrees() > W).any(), "the graph must have hub rows"
    shape = PlanShape(num_vertices=g.num_vertices,
                      padded_edges=pad_bucket(2 * g.num_directed_edges),
                      max_degree=max(2 * g.max_degree(), W + 1))
    return g, shape, W


def _plan(shape, engine_name, **spec):
    fields = dict(strategy="iterative", concurrency=64, max_rounds=256)
    fields.update(spec)
    return compile_plan(ColoringSpec(engine=engine_name, **fields), shape)


def _same(a, b):
    np.testing.assert_array_equal(a.colors, b.colors)
    assert a.rounds == b.rounds
    np.testing.assert_array_equal(a.conflicts_per_round,
                                  b.conflicts_per_round)


@pytest.mark.parametrize("spec", [
    dict(concurrency=64),                       # wavefront, frontier rounds
    dict(concurrency=64, frontier="off"),       # every round a wavefront
    dict(concurrency=1),                        # serial greedy, chaotic
    dict(strategy="dataflow"),                  # serial greedy, slab sweeps
], ids=["wavefront", "no-frontier", "serial", "dataflow"])
def test_binned_plan_matches_sort_and_greedy(binned, spec):
    g, shape, W = binned
    fused = _plan(shape, "fused_pallas", **spec)(g)
    sort = _plan(shape, "sort", **spec)(g)
    _same(fused, sort)
    assert validate_coloring(g, fused.colors)
    assert fused.colors.max() <= g.max_degree() + 1
    if spec.get("concurrency") == 1 or spec.get("strategy") == "dataflow":
        np.testing.assert_array_equal(fused.colors, greedy_color(g))
    assert fused.hub_rows == int((g.degrees() > W).sum()) > 0


def test_binned_plan_map_and_recolor(binned):
    g, shape, _ = binned
    gs = [g] + [rmat.paper_graph("RMAT-B", scale=int(np.log2(
        g.num_vertices)), seed=s) for s in (2, 3)]
    fused, sort = _plan(shape, "fused_pallas"), _plan(shape, "sort")
    for a, b, one in zip(fused.map(gs), sort.map(gs),
                         [sort(x) for x in gs]):
        _same(a, b)
        _same(a, one)
    # a seeded repair: the recolor strategy's round 0 takes the frontier
    rng = np.random.default_rng(0)
    colors = sort(g).colors.copy()
    hubs = np.flatnonzero(g.degrees() > engine.HUB_WIDTH)
    seed = np.zeros(g.num_vertices, bool)
    seed[rng.choice(g.num_vertices, 16, replace=False)] = True
    seed[hubs[:8]] = True
    colors[seed] = 0
    rf = _plan(shape, "fused_pallas", strategy="recolor")(
        g, colors=colors, seed=seed)
    rs = _plan(shape, "sort", strategy="recolor")(g, colors=colors,
                                                  seed=seed)
    _same(rf, rs)
    assert validate_coloring(g, rf.colors)


@pytest.mark.parametrize("cap_e", [0, -1, 16])
def test_binned_wavefront_spills_and_batches(binned, cap_e):
    """The binned program from a plan's own layout: chaotic full rounds
    (``cap_e`` 0), the derived windows, and windows so small that classes
    spill; one graph at a time and under ``vmap``, all the same answer."""
    g, shape, _ = binned
    gs = [g] + [rmat.paper_graph("RMAT-B", scale=int(np.log2(
        g.num_vertices)), seed=s) for s in (2,)]
    plan = _plan(shape, "fused_pallas")
    dgs = [plan._prepare(x)[0] for x in gs]
    assert dgs[0].hub_width == engine.HUB_WIDTH
    fcv, fce = resolve_frontier(
        "auto", 0, num_vertices=shape.num_vertices,
        padded_edges=shape.padded_edges, max_degree=shape.max_degree,
        has_inc=True)
    run = functools.partial(
        _iterative_impl, concurrency=64, max_rounds=256, max_sweeps=4096,
        frontier_cap_v=fcv, frontier_cap_e=fce, wavefront_cap_e=cap_e)
    batched = jax.vmap(functools.partial(
        run, backend=get_backend("fused_pallas")))(
        jax.tree.map(lambda *xs: jnp.stack(xs), *dgs))
    for i, (x, dg) in enumerate(zip(gs, dgs)):
        one = run(dg, backend=get_backend("fused_pallas"))
        for a, b in zip(one, batched):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[i])
        np.testing.assert_array_equal(np.asarray(one[0]),
                                      _plan(shape, "sort")(x).colors)
        assert (int(one[-1]) > 0) == (cap_e > 0)


def test_counters_equal_a_numpy_count(binned):
    g, shape, W = binned
    rep = _plan(shape, "fused_pallas")(g)
    deg = g.degrees()
    assert (rep.hub_rows, rep.hub_edges) == (int((deg > W).sum()),
                                             int(deg[deg > W].sum()))
    assert spans.recent_calls(1)[0].counters == {
        "wavefront_spills": rep.wavefront_spills,
        "hub_rows": rep.hub_rows, "hub_edges": rep.hub_edges}
    # the sort engine lays out no ELL slab, so nothing is binned
    assert _plan(shape, "sort")(g).hub_rows == 0


def test_envelope_within_the_bin_builds_todays_program():
    """RMAT-ER's envelope (max degree 48) fits the bin: the layout is the
    full-width slab, the counters read 0 and the program holds no hub
    path."""
    g = rmat.paper_graph("RMAT-ER", scale=10, seed=1)
    shape = PlanShape(g.num_vertices, pad_bucket(2 * g.num_directed_edges),
                      48)
    assert shape.max_degree <= engine.HUB_WIDTH
    plan = _plan(shape, "fused_pallas")
    rep = plan(g)
    assert (rep.hub_rows, rep.hub_edges) == (0, 0)
    dg = plan._prepare(g)[0]
    assert dg.ell_width == 48 and dg.hub_width == 0
    assert "hub_mex" not in str(jax.make_jaxpr(plan._executor)(dg))
    binned = PlanShape(g.num_vertices, shape.padded_edges,
                       engine.HUB_WIDTH + 1)
    bplan = _plan(binned, "fused_pallas")
    assert "hub_mex" in str(jax.make_jaxpr(bplan._executor)(
        bplan._prepare(g)[0]))
    np.testing.assert_array_equal(bplan(g).colors, rep.colors)


def test_host_layout_marks_whole_hub_rows():
    g = rmat.paper_graph("RMAT-B", scale=10, seed=1)
    W = 16
    dg = g.host_layout(layout=("edges", "ell"), pad_edges_to=pad_bucket(
        g.num_directed_edges), ell_width=W)
    src = dg.src[:g.num_directed_edges]
    slot = dg.ell_slot
    hub = g.degrees() > W
    assert (slot[:g.num_directed_edges][hub[src]] == W).all()
    assert (slot[:g.num_directed_edges][~hub[src]] < W).all()
    assert (slot[g.num_directed_edges:] == W).all()
    full = g.host_layout(layout=("edges", "ell"))
    assert full.hub_width == 0 and full.ell_width == g.max_degree()


@pytest.mark.parametrize("synthetic", [False, True],
                         ids=["bare", "synthetic"])
def test_hub_mex_matches_segment_mex(synthetic):
    """The hub path's mex (``hub=True``: a row without entries reads 1,
    no synthetic entries needed, and harmless where given) is the sort
    engine's mex with a synthetic (v, 0) entry per row."""
    rng = np.random.default_rng(7)
    mex = jax.jit(functools.partial(segment_mex, hub=True),
                  static_argnums=2)
    for _ in range(50):
        rows, m = int(rng.integers(1, 24)), int(rng.integers(1, 80))
        v = rng.integers(0, rows + 3, m).astype(np.int32)
        c = rng.integers(0, 9, m).astype(np.int32)
        full_v = np.concatenate([v, np.arange(rows)]).astype(np.int32)
        full_c = np.concatenate([c, np.zeros(rows, np.int32)])
        want = segment_mex(jnp.asarray(full_v), jnp.asarray(full_c), rows)
        if synthetic:
            v, c = full_v, full_c
        np.testing.assert_array_equal(
            np.asarray(mex(jnp.asarray(v), jnp.asarray(c), rows)),
            np.asarray(want))


def test_hub_windows_grow_by_quarters_to_cap_e():
    assert hub_windows(327680) == (5120, 20480, 81920, 327680)
    assert hub_windows(64) == (8, 16, 64)
    assert hub_windows(5) == (5,)
