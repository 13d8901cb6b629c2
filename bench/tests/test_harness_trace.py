"""The reduction from a profiler trace to busy time, op time and idle gaps:
on hand-made events, and on a small trace recorded on a TPU v5 lite."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_clips_to_the_window():
    ev = [Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 10),
          Event("d", 38, 1), Event("e", 90, 20)]
    assert tr.union(ev, 2, 100) == [(2, 15), (30, 40), (90, 100)]
    assert tr.gaps(tr.union(ev, 2, 100), 2, 100) == [(15, 30), (40, 90)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_self_time_subtracts_nested_ops():
    # a loop op (while) holding two body ops, then a plain op
    ev = [Event("while", 0, 100), Event("sort", 10, 30),
          Event("fusion", 50, 20), Event("copy", 120, 5)]
    self_ns, count = tr.self_times(ev, 0, 200)
    assert self_ns == {"while": 50, "sort": 30, "fusion": 20, "copy": 5}
    assert count == {"while": 1, "sort": 1, "fusion": 1, "copy": 1}
    # only ops that start inside the window count
    assert tr.self_times(ev, 110, 200)[0] == {"copy": 5}


def test_gaps_are_labelled_by_the_innermost_host_event():
    host = [Event("window", 0, 1_000_000), Event("plan_call", 0, 900_000),
            Event("to_device", 100_000, 300_000),
            Event("tiny", 150_000, 1_000)]
    dev = [Event("sort", 0, 100_000), Event("fusion", 400_000, 200_000),
           Event("fusion", 600_050, 50_000)]
    red = tr.reduce_trace(Trace({"/device:TPU:0": dev}, host))
    assert red.window_s == pytest.approx(1e-3)
    assert red.busy_s == pytest.approx(350_000e-9)
    # 100k-400k: the host was in to_device (tiny covers under half of it);
    # 650,050-1M: plan_call covers 249,950 of 349,950; the 50 ns gap at
    # 600k is short
    assert red.idle_by_label == pytest.approx({
        "to_device": 300e-6, "plan_call": 349_950e-9, tr.SHORT_GAP: 50e-9})
    assert red.longest_gaps[0] == ("plan_call", pytest.approx(349_950e-9))
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion", pytest.approx(250e-6)]
    assert [k for k, _ in b["idle_gaps"]][:2] == ["plan_call", "to_device"]


def test_busy_time_is_averaged_over_devices_that_ran():
    host = [Event("window", 0, 100)]
    red = tr.reduce_trace(Trace({"/device:TPU:0": [Event("a", 0, 60)],
                                 "/device:TPU:1": [Event("a", 0, 20)],
                                 "/device:TPU:2": []}, host))
    assert red.busy_s == pytest.approx(40e-9)
    assert red.op_self_s == {"a": pytest.approx(80e-9)}


def test_no_device_op_in_the_window_is_an_error():
    host = [Event("window", 0, 100)]
    with pytest.raises(ValueError, match="no device op"):
        tr.reduce_trace(Trace({"/device:TPU:0": [Event("a", 200, 5)]},
                              host))
    with pytest.raises(ValueError, match="no host span"):
        tr.find_window([Event("other", 0, 1)])


def test_kernel_time_sums_the_kernels_instructions():
    host = [Event("window", 0, 1000)]
    dev = [Event("round_fused.1", 0, 100), Event("round_fused.2", 200, 50),
           Event("scatter", 300, 10)]
    red = tr.reduce_trace(Trace({"/device:TPU:0": dev}, host))
    assert tr.kernel_time(red, "round_fused") == (pytest.approx(150e-9), 2)
    assert tr.kernel_time(red, "firstfit") == (0, 0)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of one RMAT-ER scale-10 coloring on a TPU v5 lite under
    the fused_pallas engine (19 sweeps), with the Python tracer on."""
    import gzip
    path = tmp_path_factory.mktemp("trace") / "er_s10.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "er_s10_v5e.xplane.pb.gz").read_bytes()))
    return tr.load(str(path))


def test_recorded_trace_window_busy_and_idle(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    red = tr.reduce_trace(recorded)
    assert red.window_s == pytest.approx(0.01278898, rel=1e-6)
    assert red.busy_s == pytest.approx(0.00587943, rel=1e-6)
    assert 0 < red.busy_s < red.window_s
    # idle time is the window less busy time, every gap labelled
    assert sum(red.idle_by_label.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert set(red.idle_by_label) == {
        "$api.py:202 _build_report", "$graph.py:277 to_device",
        tr.SHORT_GAP}


def test_recorded_trace_op_names_and_kernel_count(recorded):
    red = tr.reduce_trace(recorded)
    # one round_fused launch per sweep, under the kernel's jit name
    assert tr.kernel_time(red, "round_fused") == (
        pytest.approx(9.492e-06, rel=1e-6), 19)
    assert sum(red.op_count.values()) == 585
    # self times never exceed the busy time they make up
    assert sum(red.op_self_s.values()) <= red.busy_s * (1 + 1e-9)
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion.91 fusion s32[16384]",
                                  pytest.approx(0.001975045, rel=1e-6)]
    assert len(b["device_ops"]) == 10


def test_op_name_shortens_hlo_text():
    assert tr.op_name(
        '%sort.48 = (s32[8]{0:T(1024)S(1)}, s32[8]{0:T(1024)}) sort('
        's32[8]{0:T(1024)} %a), dimensions={0}') == \
        "sort.48 sort (s32[8], s32[8])"
    assert tr.op_name("%round_fused.13 = (s32[1,1024]{1,0:T(1,128)}) "
                      "custom-call(s32[32,1024]{1,0} %p)") == \
        "round_fused.13 custom-call (s32[1,1024])"
    assert tr.op_name("jit_run(123)") == "jit_run(123)"
