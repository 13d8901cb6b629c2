"""A whole run of each one-shot cell at scale 10 on the CPU, the look for a
chip skipped: sound, it is correct; with each fault planted under the
timed path, and with the control, it is not."""
import time

import pytest

from bench import faults, harness

WORKLOADS = ("rmat-g-s20.oneshot", "rmat-er-s20.oneshot")


def _run(workload, fault=None, seed=20261016):
    return harness.run(workload, seed, 0.3, False, t0=time.perf_counter(),
                       overrides={"scale": 10}, chip=False, fault=fault,
                       log=lambda s: None)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", faults.FAULTS + (faults.CONTROL,))
def test_fault_comes_out_not_correct(workload, fault):
    out = _run(workload, fault)
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
