"""Fused round kernel tests (repro.kernels.round_fused + the
``"fused_pallas"`` engine): kernel-vs-oracle equivalence, the packed-entry
bit layout, and THE ISSUE-6 guarantee — ``fused_pallas`` is bit-identical
to ``bitmap``/``ell_pallas`` across the strategy x model x frontier parity
matrix, including recolor warm starts, the distributed driver, and V=0 /
E=0 degenerates — plus the interpret-default regression pin and a
hypothesis validity property."""
import numpy as np
import pytest

from repro.core import (BipartiteGraph, ColoringSpec, Graph, color,
                        compile_plan, rmat, validate_coloring,
                        validate_d2_coloring, validate_pd2_coloring)
from repro.core.engine import get_backend, num_color_words
from repro.kernels import (CONFLICT_BIT, COLOR_MASK, FORBID_BIT, firstfit,
                           pack_entries, round_fused, round_fused_ref,
                           tile_conflict_counts)

STRATEGIES = ["iterative", "dataflow"]
MODELS = ["d1", "d2", "pd2"]
FRONTIERS = ["off", "on"]


def _graph(name="RMAT-G", scale=8, seed=1):
    return rmat.paper_graph(name, scale=scale, seed=seed)


def _bipartite(seed=0, L=120, R=80, m=600):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        L, R, np.stack([rng.integers(0, L, m), rng.integers(0, R, m)], 1))


def _assert_same_report(a, b, ctx=""):
    np.testing.assert_array_equal(a.colors, b.colors, err_msg=ctx)
    assert a.rounds == b.rounds, ctx
    np.testing.assert_array_equal(a.conflicts_per_round,
                                  b.conflicts_per_round, err_msg=ctx)
    np.testing.assert_array_equal(a.sweeps_per_round, b.sweeps_per_round,
                                  err_msg=ctx)


# ----------------------------------------------------------- kernel level
def test_pack_entries_bit_layout():
    import jax.numpy as jnp
    c = jnp.asarray([0, 7, COLOR_MASK], jnp.int32)
    ent = np.asarray(pack_entries(c, jnp.asarray([True, False, True]),
                                  jnp.asarray([False, True, True])))
    assert list(ent & COLOR_MASK) == [0, 7, COLOR_MASK]
    assert [bool(e & FORBID_BIT) for e in ent] == [True, False, True]
    assert [bool(e & CONFLICT_BIT) for e in ent] == [False, True, True]


def test_round_fused_matches_reference():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    for v, d, words in [(1, 1, 1), (37, 9, 2), (70, 17, 3)]:
        colors = rng.integers(0, 32 * words + 9, size=(v, d)).astype(np.int32)
        forbid = rng.random((v, d)) < 0.6
        elig = rng.random((v, d)) < 0.3
        own = rng.integers(0, 32 * words, size=(v,)).astype(np.int32)
        ent = pack_entries(jnp.asarray(colors), jnp.asarray(forbid),
                           jnp.asarray(elig))
        mex, conf = round_fused(ent, jnp.asarray(own), words=words,
                                block_v=16, block_d=8, interpret=True)
        rmex, rconf = round_fused_ref(ent, jnp.asarray(own), words=words)
        np.testing.assert_array_equal(np.asarray(mex), np.asarray(rmex))
        np.testing.assert_array_equal(np.asarray(conf), np.asarray(rconf))


def test_round_fused_mex_equals_firstfit():
    """With every entry FORBID and in range, the fused mex IS the firstfit
    mex — the bit-parity root of the engine guarantee."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    words = 2
    colors = rng.integers(0, 32 * words, size=(41, 11)).astype(np.int32)
    ent = pack_entries(jnp.asarray(colors), True, False)
    mex, conf = round_fused(ent, jnp.zeros((41,), jnp.int32), words=words,
                            block_v=16, block_d=8, interpret=True)
    ff = firstfit(jnp.asarray(colors), words=words, block_v=16, block_d=8,
                  interpret=True)
    np.testing.assert_array_equal(np.asarray(mex), np.asarray(ff))
    assert int(np.asarray(conf).sum()) == 0  # no CONFLICT bits packed


def test_round_fused_conflict_predicate():
    """Alg. 2 line 13 semantics: a row conflicts iff an ELIGIBLE entry
    matches its own nonzero color; uncolored rows and FORBID-only ties
    never conflict."""
    import jax.numpy as jnp
    colors = jnp.asarray([[3, 5], [3, 5], [3, 5], [0, 2]], jnp.int32)
    elig = jnp.asarray([[1, 0], [0, 0], [1, 1], [1, 1]], bool)
    ent = pack_entries(colors, True, elig)
    own = jnp.asarray([3, 3, 9, 0], jnp.int32)
    _, conf = round_fused(ent, own, words=1, block_v=8, block_d=8,
                          interpret=True)
    # row 0: eligible tie on 3 -> conflict; row 1: tie not eligible;
    # row 2: no color match; row 3: own == 0 (uncolored) never conflicts
    assert list(np.asarray(conf)) == [1, 0, 0, 0]


def test_tile_conflict_counts():
    import jax.numpy as jnp
    conf = jnp.asarray([1, 0, 1, 1, 0, 1, 0, 0, 1], jnp.int32)
    counts = np.asarray(tile_conflict_counts(conf, block_v=4))
    assert list(counts) == [3, 1, 1]
    assert counts.sum() == int(np.asarray(conf).sum())


# ------------------------------------------------- interpret default pin
def test_resolve_interpret_follows_module_default(monkeypatch):
    """Regression pin: ``interpret=None`` resolves against the backend in
    use at call time — compiled on a TPU, interpreted on the CPU, an error
    elsewhere (no silent fallback) — and the engine registry hands out
    backends with the decision already made, so no jit cache is keyed on
    ``None``."""
    import jax
    from repro.kernels import ops
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False
    assert ops.resolve_interpret(None) is True          # the CPU test path
    assert get_backend("fused_pallas").interpret is True
    assert get_backend("sort") is get_backend("sort")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    assert get_backend("ell_pallas").interpret is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="interpret=True"):
        ops.resolve_interpret(None)
    assert ops.resolve_interpret(True) is True


# ------------------------------------------------------ the parity matrix
@pytest.mark.parametrize("frontier", FRONTIERS)
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_parity_matrix(strategy, model, frontier):
    """THE tentpole guarantee: ``fused_pallas`` is bit-identical to
    ``bitmap`` — colors, rounds, conflict and sweep histories — on every
    strategy x model x frontier cell."""
    g = _bipartite() if model == "pd2" else _graph(scale=8)
    base = dict(strategy=strategy, model=model, frontier=frontier,
                lowering="square", concurrency=8, max_rounds=256)
    ref = color(g, ColoringSpec(engine="bitmap", **base))
    fused = color(g, ColoringSpec(engine="fused_pallas", **base))
    _assert_same_report(ref, fused, f"{strategy}/{model}/{frontier}")
    valid = {"d1": validate_coloring, "d2": validate_d2_coloring,
             "pd2": validate_pd2_coloring}[model]
    assert valid(g, fused.colors)


def test_fused_vs_ell_pallas_same_bitset():
    """fused_pallas and ell_pallas build the same forbidden bitset, so the
    full reports match across all three table backends."""
    g = _graph(scale=8, seed=3)
    base = dict(strategy="iterative", concurrency=16, max_rounds=256)
    reports = [color(g, ColoringSpec(engine=e, **base))
               for e in ("bitmap", "ell_pallas", "fused_pallas")]
    _assert_same_report(reports[0], reports[1])
    _assert_same_report(reports[0], reports[2])


def test_fused_distributed_parity():
    g = _graph(scale=8, seed=2)
    base = dict(strategy="distributed", concurrency=8, max_rounds=64)
    ref = color(g, ColoringSpec(engine="bitmap", **base))
    fused = color(g, ColoringSpec(engine="fused_pallas", **base))
    np.testing.assert_array_equal(ref.colors, fused.colors)
    assert ref.rounds == fused.rounds
    assert validate_coloring(g, fused.colors)


@pytest.mark.parametrize("frontier", FRONTIERS)
def test_fused_recolor_warm_parity(frontier):
    """Warm-start repair through the recolor strategy: fused and bitmap
    plans repair a seeded subset identically."""
    g = _graph(scale=8)
    base = color(g, ColoringSpec(strategy="iterative", concurrency=16))
    seed = np.zeros(g.num_vertices, bool)
    seed[:40] = True
    reps = {}
    for eng in ("bitmap", "fused_pallas"):
        plan = compile_plan(ColoringSpec(strategy="recolor", engine=eng,
                                         concurrency=16, max_rounds=64,
                                         frontier=frontier), g)
        reps[eng] = plan(g, colors=base.colors, seed=seed)
    np.testing.assert_array_equal(reps["bitmap"].colors,
                                  reps["fused_pallas"].colors)
    assert validate_coloring(g, reps["fused_pallas"].colors)


def test_fused_degenerate_graphs():
    """V=0 and E=0 graphs pass through the fused engine untouched."""
    empty = Graph.from_edges(0, np.zeros((0, 2), np.int64))
    r0 = color(empty, ColoringSpec(engine="fused_pallas"))
    assert r0.colors.shape == (0,) and r0.rounds == 0
    edgeless = Graph.from_edges(7, np.zeros((0, 2), np.int64))
    r1 = color(edgeless, ColoringSpec(engine="fused_pallas"))
    np.testing.assert_array_equal(np.asarray(r1.colors), np.ones(7))


# --------------------------------------------------------- bind contracts
def test_fused_bind_requires_ell_layout():
    backend = get_backend("fused_pallas")
    with pytest.raises(ValueError, match="ELL layout"):
        backend.bind(num_vertices=8, max_colors=4, ell_slot=None,
                     ell_width=0, max_degree=3)


def test_fused_bind_rejects_truncated_slab():
    import jax.numpy as jnp
    backend = get_backend("fused_pallas")
    with pytest.raises(ValueError, match="below the graph's max degree"):
        backend.bind(num_vertices=8, max_colors=9,
                     ell_slot=jnp.zeros((8,), jnp.int32), ell_width=2,
                     max_degree=8)
    with pytest.raises(ValueError, match="below the graph's max degree"):
        backend.bind_slab(capacity=8, max_colors=9, ell_width=2,
                          max_degree=8)


def test_fused_words_capacity_contract():
    backend = get_backend("fused_pallas")
    with pytest.raises(ValueError, match="static color bound"):
        backend.bind_slab(capacity=4, max_colors=0, ell_width=4,
                          max_degree=4)
    assert num_color_words(40) == 2  # sanity on the shared derivation


# --------------------------------------------------- hypothesis property
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - hypothesis is in requirements.txt
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def random_graphs(draw, max_v=24, max_e=60):
        n = draw(st.integers(2, max_v))
        m = draw(st.integers(0, max_e))
        edges = draw(st.lists(
            st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
            min_size=m, max_size=m))
        return Graph.from_edges(n, np.array(edges or [[0, 0]],
                                            dtype=np.int64))

    @settings(max_examples=15, deadline=None)
    @given(random_graphs(), st.sampled_from([1, 4, 16]))
    def test_fused_engine_always_valid_and_bitmap_identical(g, p):
        """Property: on arbitrary small graphs the fused engine yields a
        VALID coloring bit-identical to the bitmap engine."""
        base = dict(strategy="iterative", concurrency=p, max_rounds=256)
        ref = color(g, ColoringSpec(engine="bitmap", **base))
        fused = color(g, ColoringSpec(engine="fused_pallas", **base))
        np.testing.assert_array_equal(ref.colors, fused.colors)
        assert validate_coloring(g, fused.colors)
