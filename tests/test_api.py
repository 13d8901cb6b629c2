"""Front-door API tests (repro.core.api): ColoringSpec resolution, the
strategy registry, spec-vs-legacy bit parity across the full
strategy x engine x model matrix, ordering correctness in *original* vertex
ids, and ColoringPlan reuse/batching with ZERO recompilation (pinned via the
plan's trace counter).
"""
import dataclasses
import warnings

import numpy as np
import pytest

import jax

from repro.core import (BipartiteGraph, ColoringPlan, ColoringReport,
                        ColoringSpec, Graph, PlanShape, available_strategies,
                        color, color_dataflow, color_distributed,
                        color_iterative, compile_plan, get_strategy,
                        greedy_color, greedy_color_d2, greedy_color_pd2,
                        register_strategy, rmat, validate_coloring,
                        validate_d2_coloring, validate_pd2_coloring)
from repro.core import api as api_mod
from repro.core.api import IterativeStrategy
from repro.core.graph import pad_bucket
from repro.core.ordering import ORDERINGS

GRAPHS = ["RMAT-ER", "RMAT-G", "RMAT-B"]
STRATEGIES = ["iterative", "dataflow"]
ENGINES = ["sort", "bitmap", "ell_pallas"]
MODELS = ["d1", "d2", "pd2"]


def _graph(name="RMAT-G", scale=8, seed=1):
    return rmat.paper_graph(name, scale=scale, seed=seed)


def _bipartite(seed=0, L=120, R=80, m=600):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        L, R, np.stack([rng.integers(0, L, m), rng.integers(0, R, m)], 1))


# ----------------------------------------------------------------- registry
def test_strategies_registered():
    assert set(STRATEGIES + ["distributed"]) <= set(available_strategies())


def test_get_strategy_by_name_and_instance():
    assert get_strategy("iterative") is get_strategy("iterative")
    inst = IterativeStrategy()
    assert get_strategy(inst) is inst
    with pytest.raises(ValueError, match="unknown coloring strategy"):
        get_strategy("no-such-strategy")
    with pytest.raises(ValueError, match="already registered"):
        register_strategy(IterativeStrategy())


def test_register_custom_strategy_is_one_subclass_plus_one_call():
    """The tentpole claim: a new algorithm = subclass + register_strategy,
    and every spec/plan/report feature (ordering, report, plan) works."""

    class Alias(IterativeStrategy):
        name = "iterative-alias"

    register_strategy(Alias())
    try:
        g = _graph()
        rep = color(g, strategy="iterative-alias", concurrency=8,
                    ordering="largest_first")
        assert isinstance(rep, ColoringReport)
        assert validate_coloring(g, rep.colors)
        plan = compile_plan(ColoringSpec(strategy="iterative-alias",
                                         concurrency=8), g)
        assert validate_coloring(g, plan(g).colors)
    finally:
        api_mod._REGISTRY.pop("iterative-alias", None)


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown coloring model"):
        ColoringSpec(model="d3")
    with pytest.raises(ValueError, match="unknown lowering"):
        ColoringSpec(lowering="wedges")
    with pytest.raises(ValueError, match="unknown ordering"):
        color(_graph(), ordering="no-such-ordering")
    with pytest.raises(ValueError, match="unknown ordering"):
        compile_plan(ColoringSpec(ordering="degree"), _graph())


# ------------------------------------------------- spec vs legacy bit parity
@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("model", MODELS)
def test_spec_matches_legacy_driver(strategy, engine, model):
    """color(g, spec) is bit-identical to the legacy per-driver call for
    every strategy x engine x model cell."""
    g = _bipartite() if model == "pd2" else _graph(scale=8)
    spec = ColoringSpec(strategy=strategy, model=model, engine=engine,
                        concurrency=8, max_rounds=256)
    rep = color(g, spec)
    if strategy == "iterative":
        legacy = color_iterative(g, concurrency=8, max_rounds=256,
                                 engine=engine, model=model)
        assert rep.rounds == legacy.rounds
        np.testing.assert_array_equal(
            rep.conflicts_per_round,
            np.asarray(legacy.conflicts_per_round)[:legacy.rounds])
    else:
        legacy = color_dataflow(g, engine=engine, model=model)
        assert rep.sweeps == legacy.sweeps
    np.testing.assert_array_equal(rep.colors, np.asarray(legacy.colors))
    valid = {"d1": validate_coloring, "d2": validate_d2_coloring,
             "pd2": validate_pd2_coloring}[model]
    assert valid(g, rep.colors)


def test_spec_matches_legacy_distributed():
    g = _graph("RMAT-ER", scale=8)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    colors, rounds, conf = color_distributed(g, mesh)
    rep = color(g, strategy="distributed", mesh=mesh, max_sweeps=16384)
    np.testing.assert_array_equal(rep.colors, colors)
    assert rep.rounds == rounds
    np.testing.assert_array_equal(rep.conflicts_per_round, conf[:rounds])
    assert rep.sweeps > 0  # the unified report gains the sweep histogram


def test_report_fields_and_oracle_identity():
    g = _graph()
    rep = color(g, strategy="dataflow")
    np.testing.assert_array_equal(rep.colors, greedy_color(g))
    assert rep.rounds == 1
    assert rep.conflicts_per_round.shape == (1,)
    assert rep.sweeps_per_round.shape == (1,)
    assert rep.total_conflicts == 0
    assert rep.wall_time_s > 0
    assert "dataflow" in repr(rep)
    assert rep.num_colors == int(greedy_color(g).max())


def test_shims_are_deprecationwarning_clean():
    """The legacy entry points route through the registry without emitting
    DeprecationWarning — the CI warnings lane runs the core suite under
    ``-W error::DeprecationWarning``."""
    g = _graph(scale=7)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        color_iterative(g, concurrency=8)
        color_dataflow(g)
        color(g)


# ------------------------------------------------------ ordering correctness
@pytest.mark.parametrize("ordering", sorted(ORDERINGS))
@pytest.mark.parametrize("model", ["d1", "d2"])
def test_ordering_reports_in_original_ids(ordering, model):
    """Orderings relabel internally; the report must come back valid in the
    ORIGINAL vertex ids for every registered ordering and model."""
    g = _graph("RMAT-B", scale=8)
    rep = color(g, strategy="iterative", model=model, ordering=ordering,
                concurrency=8, max_rounds=256, ordering_seed=3)
    valid = validate_coloring if model == "d1" else validate_d2_coloring
    assert valid(g, rep.colors)


def test_ordering_dataflow_equals_serial_greedy_in_that_order():
    """DATAFLOW + ordering == serial greedy visited in that order (the
    un-relabeling is exact, not merely validity-preserving)."""
    from repro.core import ordering as ordering_mod
    g = _graph("RMAT-G", scale=8)
    for name in ["largest_first", "smallest_last", "random"]:
        rep = color(g, strategy="dataflow", ordering=name, ordering_seed=5)
        order = ORDERINGS[name](g, 5)
        perm = np.empty_like(order)
        perm[order] = np.arange(order.shape[0])
        want = greedy_color(ordering_mod.apply(g, order))[perm]
        np.testing.assert_array_equal(rep.colors, want)
        assert validate_coloring(g, rep.colors)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is in requirements.txt
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def random_graphs(draw, max_v=32, max_e=90):
        n = draw(st.integers(2, max_v))
        m = draw(st.integers(0, max_e))
        edges = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        return Graph.from_edges(n, np.array(edges or [[0, 0]],
                                            dtype=np.int64))

    @settings(max_examples=12, deadline=None)
    @given(random_graphs(), st.sampled_from(sorted(ORDERINGS)),
           st.integers(0, 4))
    def test_plan_ordering_property(g, ordering, seed):
        """Property: a PLAN with any registered ordering returns a valid
        coloring in original vertex ids, bounded by degeneracy-style color
        counts (<= Delta+1)."""
        spec = ColoringSpec(strategy="dataflow", ordering=ordering,
                            ordering_seed=seed)
        rep = compile_plan(spec, g)(g)
        assert validate_coloring(g, rep.colors)
        assert rep.num_colors <= g.max_degree() + 1


# ------------------------------------------------------- plans: reuse, map
def test_pad_bucket_grid():
    assert pad_bucket(0) == 0   # degenerate: no phantom minimum bucket
    assert pad_bucket(1) == 256
    assert pad_bucket(256) == 256
    assert pad_bucket(257) == 320  # step 2^6 inside the (256, 512] octave
    for n in [300, 1000, 5000, 123456]:
        b = pad_bucket(n)
        assert b >= n
        assert b <= n * 1.25 + 1
        assert pad_bucket(b) == b  # buckets are fixed points


def test_plan_zero_retrace_across_same_bucket_graphs():
    """THE plan guarantee: a second same-bucket graph triggers zero
    recompilation (the trace counter stays at one)."""
    spec = ColoringSpec(strategy="iterative", engine="bitmap", concurrency=8)
    gs = [_graph("RMAT-G", scale=8, seed=s) for s in range(4)]
    shape = PlanShape(
        num_vertices=gs[0].num_vertices,
        padded_edges=pad_bucket(max(g.num_directed_edges for g in gs)),
        max_degree=max(g.max_degree() for g in gs))
    plan = compile_plan(spec, shape)
    assert plan.traces == 0
    reports = [plan(g) for g in gs]
    assert plan.traces == 1
    for g, rep in zip(gs, reports):
        assert validate_coloring(g, rep.colors)
        legacy = color_iterative(g, concurrency=8, engine="bitmap")
        np.testing.assert_array_equal(rep.colors, np.asarray(legacy.colors))
        assert rep.rounds == legacy.rounds


def test_plan_map_matches_python_loop():
    """plan.map (one vmapped program) == the per-graph python loop, and
    both stay on the compiled-once path."""
    spec = ColoringSpec(strategy="iterative", engine="sort", concurrency=8)
    gs = [_graph("RMAT-ER", scale=8, seed=s) for s in range(3)]
    shape = PlanShape(
        num_vertices=gs[0].num_vertices,
        padded_edges=pad_bucket(max(g.num_directed_edges for g in gs)),
        max_degree=max(g.max_degree() for g in gs))
    plan = compile_plan(spec, shape)
    looped = [plan(g) for g in gs]
    mapped = plan.map(gs)
    assert plan.traces == 2  # one per-graph trace + one vmapped trace
    for one, many in zip(looped, mapped):
        np.testing.assert_array_equal(one.colors, many.colors)
        assert one.rounds == many.rounds
        np.testing.assert_array_equal(one.conflicts_per_round,
                                      many.conflicts_per_round)
        np.testing.assert_array_equal(one.sweeps_per_round,
                                      many.sweeps_per_round)
    # a second same-size batch reuses the vmapped program too
    plan.map(list(reversed(gs)))
    assert plan.traces == 2
    assert plan.map([]) == []


def test_plan_map_with_ordering_unrelabels_per_graph():
    spec = ColoringSpec(strategy="dataflow", ordering="largest_first")
    gs = [_graph("RMAT-B", scale=7, seed=s) for s in range(2)]
    shape = PlanShape(
        num_vertices=gs[0].num_vertices,
        padded_edges=pad_bucket(max(g.num_directed_edges for g in gs)),
        max_degree=max(g.max_degree() for g in gs))
    mapped = compile_plan(spec, shape).map(gs)
    for g, rep in zip(gs, mapped):
        assert validate_coloring(g, rep.colors)


def test_plan_d2_model_and_oracle():
    g = _graph("RMAT-ER", scale=7)
    plan = compile_plan(ColoringSpec(strategy="dataflow", model="d2"), g)
    rep = plan(g)
    np.testing.assert_array_equal(rep.colors, greedy_color_d2(g))
    assert plan.traces == 1


def test_plan_pd2_model_and_oracle():
    bg = _bipartite()
    plan = compile_plan(ColoringSpec(strategy="dataflow", model="pd2"), bg)
    rep = plan(bg)
    np.testing.assert_array_equal(rep.colors, greedy_color_pd2(bg))
    assert validate_pd2_coloring(bg, rep.colors)


def test_plan_ell_pallas_zero_retrace():
    spec = ColoringSpec(strategy="iterative", engine="ell_pallas",
                        concurrency=8)
    g0, g1 = (_graph("RMAT-ER", scale=7, seed=s) for s in (0, 1))
    shape = PlanShape(num_vertices=g0.num_vertices,
                      padded_edges=pad_bucket(max(g0.num_directed_edges,
                                                  g1.num_directed_edges)),
                      max_degree=max(g0.max_degree(), g1.max_degree()))
    plan = compile_plan(spec, shape)
    for g in (g0, g1):
        assert validate_coloring(g, plan(g).colors)
    assert plan.traces == 1


def test_distributed_plan_reuse():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("x",))
    spec = ColoringSpec(strategy="distributed", mesh=mesh, max_sweeps=16384)
    gs = [_graph("RMAT-ER", scale=8, seed=s) for s in (3, 4)]
    plan = compile_plan(spec, gs[0])
    for g in gs:
        rep = plan(g)
        assert validate_coloring(g, rep.colors)
        colors, rounds, _ = color_distributed(g, mesh)
        np.testing.assert_array_equal(rep.colors, colors)
        assert rep.rounds == rounds
    assert plan.traces == 1
    with pytest.raises(NotImplementedError, match="plan.map"):
        plan.map(gs)


def test_plan_shape_rejections():
    spec = ColoringSpec(strategy="iterative", concurrency=8)
    n = 300
    ring = Graph.from_edges(
        n, np.stack([np.arange(n), (np.arange(n) + 1) % n], 1))
    plan = compile_plan(spec, ring)
    # wrong vertex count
    with pytest.raises(ValueError, match="compile a new plan"):
        plan(_graph(scale=8))
    # same V, too many constraint edges for the bucket
    rng = np.random.default_rng(0)
    dense = Graph.from_edges(
        n, np.stack([rng.integers(0, n, 4000), rng.integers(0, n, 4000)], 1))
    with pytest.raises(ValueError, match="above the plan bucket"):
        plan(dense)
    # same V, edges within bucket, but a hub above the degree bound
    star = Graph.from_edges(
        n, np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], 1))
    with pytest.raises(ValueError, match="exceeds the plan bound"):
        plan(star)
    # plans want host graphs (they relabel/pad on host)
    with pytest.raises(TypeError, match="host Graph"):
        compile_plan(spec, ring.to_device())
    with pytest.raises(ValueError, match="relabels on host"):
        color(ring.to_device(), ordering="random")


# ------------------------------------------------- introspection and cache
def test_plan_lower_exposes_the_compiled_program():
    """``plan.lower(g)`` is the exact program ``plan(g)`` runs: compiling
    it serves the call (no second trace), and its HLO is inspectable —
    on a TPU that is where a Pallas engine's ``tpu_custom_call`` shows."""
    g = _graph(scale=7)
    plan = compile_plan(ColoringSpec(engine="fused_pallas"), g)
    assert plan.backend.interpret is True            # the CPU test path
    text = plan.lower(g).compile().as_text()
    assert "while" in text
    assert validate_coloring(g, plan(g).colors)
    assert plan.traces == 1
    dist = compile_plan(ColoringSpec(strategy="distributed"), g)
    with pytest.raises(NotImplementedError, match="plan.lower"):
        dist.lower(g)


def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory
    (nothing is set in code); otherwise the fixed in-checkout path."""
    from repro import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert compile_cache.enable_compile_cache() == "/cache/from/env"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert path.endswith(".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.enable_compile_cache() == path   # fixed, not fresh
