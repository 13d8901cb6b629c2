"""The peaks table and the work a coloring's sweeps need."""
import pytest

from bench import roofline


def test_peaks_of_v5e_and_unknown_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_sweep_bytes_hand_counted():
    # a path 0-1-2-3 plus an isolated vertex: V=5, 3 undirected edges,
    # 6 directed entries, mean degree 6/5
    # round 0: 3 sweeps over all 5 vertices: 3 * 4 * (6 + 3*5) = 252
    # round 1: 2 sweeps over the 2 vertices round 0 queued:
    #          2 * 4 * (2 * 6/5 + 3*2) = 67.2
    # round 2: 1 sweep over the 1 vertex round 1 queued:
    #          1 * 4 * (6/5 + 3) = 16.8
    got = roofline.sweep_bytes(num_vertices=5, num_directed_edges=6,
                               sweeps_per_round=[3, 2, 1],
                               conflicts_per_round=[2, 1, 0])
    assert got == pytest.approx(252 + 67.2 + 16.8)


def test_sweep_bytes_ignores_padding_and_counts_work_only():
    one = roofline.sweep_bytes(num_vertices=1 << 20,
                               num_directed_edges=16 << 20,
                               sweeps_per_round=[10],
                               conflicts_per_round=[0])
    assert one == 10 * 4 * ((16 << 20) + 3 * (1 << 20))
    assert roofline.sweep_bytes(num_vertices=8, num_directed_edges=0,
                                sweeps_per_round=[],
                                conflicts_per_round=[]) == 0
