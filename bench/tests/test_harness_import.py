"""Importing the benchmark initialises no backend, and without a TPU a run
exits non-zero and prints no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    return env


def test_import_touches_no_backend():
    code = (
        "import bench, bench.cells, bench.harness, bench.loops, "
        "bench.reference, bench.rmat, bench.roofline, bench.trace_reduce, "
        "bench.faults, bench.control, bench.kinds.oneshot\n"
        "from bench import cells\n"
        "for m in cells.load_json(cells.BENCHMARK_FILE)['per_layer']:\n"
        "    cells.reader(m['name'])\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n"
        "print('clean')\n")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().endswith("clean")


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "rmat-g-s20.oneshot", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=_env(), capture_output=True, text=True,
        timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result line: {line}")
