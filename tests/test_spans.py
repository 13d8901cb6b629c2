"""Spans of the plan call (repro.core.spans): nesting and self time, call
ids, per-thread stacks, the ring's bound, errors, spans outside a call,
and the record one ``plan(g)`` leaves."""
import threading

import pytest

from repro.core import ColoringSpec, compile_plan, rmat, spans


class FakeClock:
    """Nanoseconds that advance only when told to."""

    def __init__(self):
        self.now = 0

    def __call__(self):
        return self.now

    def tick(self, ns):
        self.now += ns


def test_nesting_and_self_time():
    clock = FakeClock()
    rec = spans.Recorder(clock=clock)
    with rec.call("root"):
        clock.tick(10)
        with rec.span("a"):
            clock.tick(30)
            with rec.span("a.inner"):
                clock.tick(5)
        with rec.span("b"):
            clock.tick(20)
        clock.tick(7)
    (c,) = rec.recent_calls(1)
    assert [(s.name, s.parent) for s in c.spans] == [
        ("root", -1), ("a", 0), ("a.inner", 1), ("b", 0)]
    assert [s.duration_ns for s in c.spans] == [72, 35, 5, 20]
    assert [c.self_ns(i) for i in range(4)] == [17, 30, 5, 20]
    assert c.total_ns("a") == 35 and c.total_ns("missing") == 0
    assert c.root.name == "root" and not c.error


def test_one_call_id_per_root_span():
    rec = spans.Recorder()
    for _ in range(3):
        with rec.call("root"):
            with rec.span("a"):
                # a call inside a call is a span of the outer one
                with rec.call("nested"):
                    pass
    calls = rec.recent_calls(10)
    assert len(calls) == 3
    assert len({c.call_id for c in calls}) == 3
    for c in calls:
        assert [s.name for s in c.spans] == ["root", "a", "nested"]
        assert {s.call_id for s in c.spans} == {c.call_id}


def test_each_thread_has_its_own_stack():
    rec = spans.Recorder()
    inside = threading.Barrier(4, timeout=30)

    def work(i):
        with rec.call(f"root{i}"):
            with rec.span(f"a{i}"):
                inside.wait()   # every thread holds a span open at once
                with rec.span(f"b{i}"):
                    inside.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    calls = rec.recent_calls(10)
    assert len(calls) == 4
    for c in calls:
        i = c.root.name[len("root"):]
        assert [(s.name, s.parent) for s in c.spans] == [
            (f"root{i}", -1), (f"a{i}", 0), (f"b{i}", 1)]


def test_the_ring_keeps_the_newest_calls():
    rec = spans.Recorder(capacity=3)
    for i in range(5):
        with rec.call(f"call{i}"):
            pass
    assert [c.root.name for c in rec.recent_calls(10)] == [
        "call2", "call3", "call4"]
    assert [c.root.name for c in rec.recent_calls(2)] == ["call3", "call4"]
    assert rec.recent_calls(0) == []


def test_an_exception_closes_its_span_with_error():
    clock = FakeClock()
    rec = spans.Recorder(clock=clock)
    with pytest.raises(KeyError):
        with rec.call("root"):
            with rec.span("ok"):
                clock.tick(1)
            with rec.span("bad"):
                clock.tick(2)
                raise KeyError("boom")
    (c,) = rec.recent_calls(1)
    assert [(s.name, s.error) for s in c.spans] == [
        ("root", True), ("ok", False), ("bad", True)]
    assert c.error and all(s.end_ns >= s.start_ns for s in c.spans)
    assert c.spans[2].duration_ns == 2
    # the stack unwound: a span now is outside any call again
    with rec.span("after"):
        pass
    assert rec.recent_calls(10) == [c]


def test_a_span_outside_any_call_leaves_no_record():
    rec = spans.Recorder()
    with rec.span("alone"):
        with rec.span("nested"):
            pass
    assert rec.recent_calls(10) == []


def test_a_plan_call_leaves_one_record_of_its_phases():
    g = rmat.paper_graph("RMAT-G", scale=10, seed=0)
    plan = compile_plan(ColoringSpec(), g)
    before = spans.recent_calls(1)
    report = plan(g)
    (c,) = spans.recent_calls(1)
    assert not before or c.call_id != before[0].call_id
    assert c.root.name == "plan.call" and not c.error
    children = [s.name for s in c.spans if s.parent == 0]
    assert children == ["plan.canonicalize", "plan.layout", "plan.transfer",
                        "plan.device", "plan.report"]
    assert len(c.spans) == 6
    assert 0 < c.total_ns("plan.device") <= c.total_ns("plan.call")
    assert c.self_ns(0) >= 0
    # the report's wall time is the call span's
    assert report.wall_time_s == pytest.approx(c.root.duration_ns * 1e-9)
