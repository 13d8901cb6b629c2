"""The chip benchmark of the coloring system: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, in one process that owns the chip. The
cell, its configuration, traffic and metrics are named in
``BENCHMARK.json``. The run builds the seed's graph and warms every
program (set-up), measures for ``--seconds`` (the window), then checks
every answer against the plain reference. The last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones,
read from a profiler trace of the window), ``device`` and, last,
``checks``: each number compared with its limit. Without a TPU it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness
    try:
        print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t0=T0,
                          log=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 2
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
