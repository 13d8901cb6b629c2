"""The control, on the chip: the cell's own run with the program's conflict
resolution cut to one round (``faults.CONTROL``), or with a named fault of
``faults.py`` planted, over several seeds in one process. It prints each
run's compared numbers; every one of them has to come out not correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10
        [--fault speculation_only|unchanged_state|half_batch|altered_answer]

The benchmark's own runs never run it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default="speculation_only")
    args = ap.parse_args(argv)
    from bench import harness
    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    caught = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False, t0=t0,
                          fault=args.fault,
                          log=lambda s: print(s, flush=True))
        caught.append(not out["correct"])
        print(json.dumps({"control": args.fault, "workload": args.workload,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "checks": out["checks"]}),
              flush=True)
    return 0 if all(caught) else 1


if __name__ == "__main__":
    sys.exit(main())
