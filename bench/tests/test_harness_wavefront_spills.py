"""``wavefront_spills``, read from the counter each ``plan.call`` record
of the program keeps: a traced run of each one-shot cell at scale 10 on the
CPU reports it, the reader takes exactly the window's calls, and a program
without the counter reads nothing."""
import sys
import time
import types

import pytest

from bench import cells, harness
from bench.loops import Record
from repro.core import spans

WORKLOADS = ("rmat-g-s20.oneshot", "rmat-er-s20.oneshot")
READ = cells.reader("wavefront_spills")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_traced_run_reports_the_spills(workload, monkeypatch, tmp_path):
    # a trace directory of its own: other test files run traced cells in
    # parallel workers, and the harness clears its directory per run
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out = harness.run(workload, 3405691582, 0.3, True,
                      t0=time.perf_counter(), overrides={"scale": 10},
                      chip=False, log=lambda s: None)
    assert out["correct"] is True
    assert out["metrics"]["wavefront_spills"] == {"value": 0.0,
                                                  "unit": "classes"}


def _recorder(monkeypatch, counts):
    """A recorder holding one ``plan.call`` per entry of ``counts``
    (``None``: a call that counted nothing), oldest first."""
    rec = spans.Recorder()
    for n in counts:
        with rec.call("plan.call"):
            if n is not None:
                rec.count("wavefront_spills", n)
    monkeypatch.setattr(spans, "recent_calls", rec.recent_calls)


def test_reader_takes_exactly_the_windows_calls(monkeypatch):
    # the warm call first, then the window's three
    _recorder(monkeypatch, [9, 0, 2, 1])
    assert READ(Record({}, 8, attempted=3)) == pytest.approx(1.0)


def test_counts_add_within_a_call_and_not_outside_one():
    rec = spans.Recorder()
    rec.count("wavefront_spills", 5)           # no call open: dropped
    with rec.call("plan.call"):
        rec.count("wavefront_spills", 1)
        with rec.span("plan.report"):
            rec.count("wavefront_spills", 2)
    assert rec.recent_calls(1)[0].counters == {"wavefront_spills": 3}


def test_calls_without_the_counter_read_nothing(monkeypatch):
    _recorder(monkeypatch, [0, None])
    assert READ(Record({}, 8, attempted=2)) is None
    assert READ(Record({}, 8, attempted=3)) is None     # too few calls


def test_a_program_without_counters_reads_nothing(monkeypatch):
    """Call records as a program that keeps spans but no counters has
    them: the reader finds nothing and does not raise."""
    old = types.SimpleNamespace(root=types.SimpleNamespace(name="plan.call"))
    monkeypatch.setattr(spans, "recent_calls", lambda n: [old] * n)
    assert READ(Record({}, 8, attempted=2)) is None
    monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    monkeypatch.delattr("repro.core.spans", raising=False)
    assert READ(Record({}, 8, attempted=1)) is None
