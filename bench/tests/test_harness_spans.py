"""The front door's metrics, read from the program's own spans: a traced
run of each one-shot cell at scale 10 on the CPU reports them, and each
reader takes exactly the window's calls."""
import sys
import time

import pytest

from bench import cells, harness
from bench.loops import Record
from repro.core import spans

WORKLOADS = ("rmat-g-s20.oneshot", "rmat-er-s20.oneshot")
READERS = ("front_door_ms", "upload_ms")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_traced_run_reports_the_front_door(workload):
    out = harness.run(workload, 20261018, 0.3, True, t0=time.perf_counter(),
                      overrides={"scale": 10}, chip=False,
                      log=lambda s: None)
    assert out["correct"] is True
    for name in READERS:
        assert out["metrics"][name]["unit"] == "ms"
        assert out["metrics"][name]["value"] > 0


def _recorder(monkeypatch, calls):
    """A recorder holding ``calls`` (each ``(call_ms, device_ms,
    transfer_ms)``), oldest first, in place of the program's."""
    clock = [0]
    rec = spans.Recorder(clock=lambda: clock[0])
    for call_ms, device_ms, transfer_ms in calls:
        with rec.call("plan.call"):
            with rec.span("plan.transfer"):
                clock[0] += int(transfer_ms * 1e6)
            with rec.span("plan.device"):
                clock[0] += int(device_ms * 1e6)
            clock[0] += int((call_ms - device_ms - transfer_ms) * 1e6)
    monkeypatch.setattr(spans, "recent_calls", rec.recent_calls)
    return rec


def test_readers_take_exactly_the_windows_calls(monkeypatch):
    # the warm call first, far slower than the window's two
    _recorder(monkeypatch, [(9000, 100, 5000), (50, 30, 4), (70, 40, 8)])
    rec = Record({}, 8, attempted=2)
    assert cells.reader("front_door_ms")(rec) == pytest.approx(25.0)
    assert cells.reader("upload_ms")(rec) == pytest.approx(6.0)


@pytest.mark.parametrize("name", READERS)
def test_too_few_records_read_nothing(monkeypatch, name):
    _recorder(monkeypatch, [(50, 30, 4), (70, 40, 8)])
    assert cells.reader(name)(Record({}, 8, attempted=3)) is None
    assert cells.reader(name)(Record({}, 8, attempted=0)) is None


@pytest.mark.parametrize("name", READERS)
def test_other_roots_read_nothing(monkeypatch, name):
    rec = spans.Recorder()
    with rec.call("something.else"):
        pass
    monkeypatch.setattr(spans, "recent_calls", rec.recent_calls)
    assert cells.reader(name)(Record({}, 8, attempted=1)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.delattr("repro.core.spans", raising=False)
    assert cells.reader(name)(Record({}, 8, attempted=1)) is None
