"""Each per-layer metric's reader, on a hand-made record: the number it
reads, and nothing where it finds nothing to read."""
import pytest

from bench import cells
from bench.loops import Record
from bench.trace_reduce import Reduction

PEAKS = {"hbm_bytes_per_s": 819e9}


def _reduction(busy_s=3.0, window_s=4.0, kernel_s=0.5):
    return Reduction(window_s=window_s, busy_s=busy_s,
                     op_self_s={"round_fused.3 custom-call (s32[1,1024])":
                                kernel_s, "sort.1 sort (s32[8])": 1.0},
                     op_count={"round_fused.3 custom-call (s32[1,1024])": 4,
                               "sort.1 sort (s32[8])": 2},
                     idle_by_label={}, longest_gaps=[])


def _coloring(sweeps, conflicts, edges=16 << 20):
    return dict(num_directed_edges=edges, rounds=len(sweeps),
                sweeps_per_round=sweeps, conflicts_per_round=conflicts,
                frontier_sizes_per_round=[0] * len(sweeps))


def _oneshot_record(reduction=None):
    rec = Record({}, 1 << 20, attempted=2, window_s=4.0)
    rec.colorings = [_coloring([30, 6, 2], [9000, 50, 0]),
                     _coloring([29, 5, 2], [8000, 40, 0])]
    rec.reduction, rec.peaks = reduction, PEAKS
    return rec


def test_device_idle():
    read = cells.reader("device_idle.oneshot")
    assert read(_oneshot_record(_reduction())) == pytest.approx(25.0)
    assert read(_oneshot_record()) is None


def test_sweeps_is_the_mean_per_coloring():
    read = cells.reader("sweeps")
    assert read(_oneshot_record()) == pytest.approx(37.0)
    assert read(Record({}, 8)) is None


def test_device_ms_per_sweep():
    read = cells.reader("device_ms_per_sweep")
    assert read(_oneshot_record(_reduction(busy_s=3.7))) == \
        pytest.approx(3700 / 74)
    assert read(_oneshot_record()) is None


def test_round_fused_roofline_counts_work_over_kernel_time():
    from bench.roofline import sweep_bytes
    read = cells.reader("round_fused_roofline")
    rec = _oneshot_record(_reduction(kernel_s=0.5))
    least_s = sum(sweep_bytes(
        num_vertices=1 << 20, num_directed_edges=c["num_directed_edges"],
        sweeps_per_round=c["sweeps_per_round"],
        conflicts_per_round=c["conflicts_per_round"])
        for c in rec.colorings) / PEAKS["hbm_bytes_per_s"]
    assert read(rec) == pytest.approx(100 * least_s / 0.5)
    # no kernel in the trace: nothing to read, never 0
    no_kernel = _reduction()._replace(op_self_s={"sort.1": 1.0},
                                      op_count={"sort.1": 1})
    assert read(_oneshot_record(no_kernel)) is None
    assert read(_oneshot_record()) is None

