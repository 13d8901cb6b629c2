"""The binned fused engine's metrics, ``hub_edge_share``, ``hub_mex_ms``
and ``binned_mex_roofline``: a traced run of ``rmat-b-s20.oneshot`` at scale
10 on the CPU is correct and reports the share, each reader reads its value
on a hand-made record, and nothing where the program keeps no counter or
the trace holds no such op."""
import sys
import time
import types

import pytest

from bench import cells, faults, harness
from bench.loops import Record
from bench.trace_reduce import Reduction
from repro.core import engine, spans

WORKLOAD = "rmat-b-s20.oneshot"
PEAKS = {"hbm_bytes_per_s": 819e9}
SHARE = cells.reader("hub_edge_share")
HUB_MS = cells.reader("hub_mex_ms")
BINNED = cells.reader("binned_mex_roofline")


def _run(monkeypatch, tmp_path, fault=None, trace=False):
    # a narrow bin, so that scale-10 RMAT-B graphs have hub rows; a trace
    # directory of its own, as other files run traced cells in parallel
    monkeypatch.setattr(engine, "HUB_WIDTH", 16)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    return harness.run(WORKLOAD, 2654435761, 0.3, trace,
                       t0=time.perf_counter(), overrides={"scale": 10},
                       chip=False, fault=fault, log=lambda s: None)


def test_a_traced_run_is_correct_and_reports_the_share(monkeypatch,
                                                       tmp_path):
    out = _run(monkeypatch, tmp_path, trace=True)
    assert out["correct"] is True and out["failed"] == 0
    share = out["metrics"]["hub_edge_share"]
    assert share["unit"] == "%" and 0 < share["value"] < 100


@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CONTROL,))
def test_fault_comes_out_not_correct(monkeypatch, tmp_path, fault):
    out = _run(monkeypatch, tmp_path, fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _coloring(edges, sweeps=(1, 2, 2), conflicts=(9000, 50, 0)):
    return dict(num_directed_edges=edges, rounds=len(sweeps),
                sweeps_per_round=list(sweeps),
                conflicts_per_round=list(conflicts),
                frontier_sizes_per_round=[0] * len(sweeps))


def _counted(monkeypatch, hub_edges):
    """A recorder holding one ``plan.call`` per entry (``None``: a call
    that counted nothing), oldest first, in place of the program's."""
    rec = spans.Recorder()
    for n in hub_edges:
        with rec.call("plan.call"):
            if n is not None:
                rec.count("hub_edges", n)
    monkeypatch.setattr(spans, "recent_calls", rec.recent_calls)


def test_share_is_the_mean_over_the_windows_calls(monkeypatch):
    # the warm call first, then the window's two
    _counted(monkeypatch, [7, 300, 100])
    rec = Record({}, 8, attempted=2)
    rec.colorings = [_coloring(1000), _coloring(400)]
    assert SHARE(rec) == pytest.approx(100 * (0.3 + 0.25) / 2)


def test_share_reads_nothing_without_the_counter(monkeypatch):
    _counted(monkeypatch, [300, None])
    rec = Record({}, 8, attempted=2)
    rec.colorings = [_coloring(1000), _coloring(1000)]
    assert SHARE(rec) is None
    old = types.SimpleNamespace(root=types.SimpleNamespace(name="plan.call"))
    monkeypatch.setattr(spans, "recent_calls", lambda n: [old] * n)
    assert SHARE(rec) is None
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.delattr("repro.core.spans", raising=False)
    assert SHARE(rec) is None


def _reduction(ops):
    """A reduction whose ops are ``{name: (self seconds, count)}``."""
    return Reduction(window_s=4.0, busy_s=3.0,
                     op_self_s={k: s for k, (s, _) in ops.items()},
                     op_count={k: n for k, (_, n) in ops.items()},
                     idle_by_label={}, longest_gaps=[])


KERNEL = "round_fused.3 custom-call (s32[1,1024])"
HUB = "hub_mex.24 sort (s32[81920], s32[81920])"


def _record(ops):
    rec = Record({}, 1 << 20, attempted=2, window_s=4.0)
    rec.colorings = [_coloring(16 << 20), _coloring(16 << 20)]
    rec.reduction, rec.peaks = _reduction(ops), PEAKS
    return rec


def test_hub_mex_ms_is_the_hub_ops_time_per_coloring():
    rec = _record({KERNEL: (0.5, 4), HUB: (0.03, 128),
                   "sort.1 sort (s32[8])": (1.0, 2)})
    assert HUB_MS(rec) == pytest.approx(1e3 * 0.03 / 2)
    assert HUB_MS(_record({KERNEL: (0.5, 4)})) is None
    assert HUB_MS(Record({}, 8)) is None


def test_binned_roofline_counts_both_paths_and_is_the_kernels_alone():
    alone = _record({KERNEL: (0.5, 4), "sort.1 sort (s32[8])": (1.0, 2)})
    assert BINNED(alone) == pytest.approx(
        cells.reader("round_fused_roofline")(alone))
    both = _record({KERNEL: (0.5, 4), HUB: (0.25, 128)})
    assert BINNED(both) == pytest.approx(BINNED(alone) * 0.5 / 0.75)
    assert BINNED(_record({"sort.1 sort (s32[8])": (1.0, 2)})) is None
    assert BINNED(Record({}, 8)) is None
