"""The benchmark's R-MAT generator at scale 10, each family."""
import numpy as np
import pytest

from bench import rmat

FAMILIES = {"RMAT-ER": (0.25, 0.25, 0.25, 0.25),
            "RMAT-G": (0.45, 0.15, 0.15, 0.25),
            "RMAT-B": (0.55, 0.15, 0.15, 0.15)}


def _generate(family, seed=5, shuffle=True):
    return rmat.generate(seed, scale=10, edge_factor=8,
                         rmat=FAMILIES[family], shuffle=shuffle)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scale10_graph_is_symmetric_and_deduplicated(family):
    pairs, _, g = _generate(family)
    assert pairs.shape == (8 << 10, 2) and pairs.dtype == np.int32
    assert pairs.min() >= 0 and pairs.max() < 1 << 10
    assert g.row_ptr.dtype == np.int64 and g.col_idx.dtype == np.int32
    src, dst = g.directed_edges()
    assert not (src == dst).any()
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert len(fwd) == src.size                      # no duplicates
    assert fwd == set(zip(dst.tolist(), src.tolist()))  # symmetric
    # every distinct non-loop raw pair is an edge, and nothing else is
    u = np.minimum(pairs[:, 0], pairs[:, 1])
    v = np.maximum(pairs[:, 0], pairs[:, 1])
    raw = set(zip(u[u != v].tolist(), v[u != v].tolist()))
    assert raw == {(a, b) for a, b in fwd if a < b}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_csr_is_the_programs_own_build_of_the_pairs(family):
    from repro.core.graph import Graph
    pairs, _, g = _generate(family, seed=2**31 + 5)
    ref = Graph.from_edges(1 << 10, pairs)
    assert np.array_equal(g.row_ptr, ref.row_ptr)
    assert np.array_equal(g.col_idx, ref.col_idx)


def test_max_degree_orders_er_g_b():
    deg = {f: _generate(f).graph.max_degree() for f in FAMILIES}
    assert deg["RMAT-ER"] < deg["RMAT-G"] < deg["RMAT-B"]


def test_same_seed_same_graph_and_large_seeds():
    a = _generate("RMAT-G", 2**31 + 17).pairs
    b = _generate("RMAT-G", 2**31 + 17).pairs
    c = _generate("RMAT-G", 2**31 + 18).pairs
    d = _generate("RMAT-G", 2**31 + 17 + 2**32).pairs
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c) and not np.array_equal(a, d)


def test_shuffle_is_a_relabelling_of_the_unshuffled_graph():
    plain = _generate("RMAT-B", 9, shuffle=False)
    shuffled = _generate("RMAT-B", 9)
    assert np.array_equal(plain.perm, np.arange(1 << 10))
    assert np.array_equal(shuffled.perm[plain.pairs], shuffled.pairs)
    assert np.array_equal(np.sort(shuffled.perm), np.arange(1 << 10))


def test_quadrant_shares_follow_the_parameters():
    pairs = rmat.sample_pairs(3, rmat.GRAPH_STREAM, scale=1, count=200_000,
                              params=FAMILIES["RMAT-G"])
    quad = pairs[:, 0] * 2 + pairs[:, 1]
    shares = np.bincount(quad, minlength=4) / quad.size
    assert np.allclose(shares, FAMILIES["RMAT-G"], atol=0.005)


def test_streams_and_indices_differ():
    draw = [rmat.sample_pairs(7, stream, index, scale=10, count=64,
                              params=FAMILIES["RMAT-ER"])
            for stream, index in ((rmat.GRAPH_STREAM, 0),
                                  (rmat.GRAPH_STREAM, 1),
                                  (rmat.SHUFFLE_STREAM, 0))]
    assert not np.array_equal(draw[0], draw[1])
    assert not np.array_equal(draw[0], draw[2])


def test_parameters_must_sum_to_one():
    with pytest.raises(ValueError, match="sum to 1"):
        rmat.sample_pairs(1, 1, scale=2, count=4, params=(0.5, 0.5, 0.5, 0))
