"""One run of one cell: set-up, the measured window, the reference check,
and the result line. ``run.py`` is its command line."""
from __future__ import annotations

import json
import shutil
import sys
import time
from typing import Optional

from . import cells, roofline, trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_DIR = cells.ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, too few chips, or a chip the peaks table lacks."""


def require_chip(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    try:
        return devices[:chips], roofline.peaks(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
    keeping every program, however quick to compile, so that only a
    checkout's first run compiles."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts XLA backend compilations while it is open."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.count += 1

    def close(self) -> int:
        self._mon.unregister_event_duration_listener(self._on)
        return self.count


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: float, overrides: Optional[dict] = None, chip: bool = True,
        fault: Optional[str] = None, log=print) -> dict:
    """Run one cell and return its result line as a dict. ``t0`` is the
    process's start on ``time.perf_counter``. ``chip=False`` skips the
    look for a chip (tests only); ``fault`` plants a fault of
    ``faults.py`` under the timed path (tests and the control only)."""
    cell = cells.find_cell(workload, overrides)
    import jax
    if chip:
        devices, peaks = require_chip(cell.chips)
    else:
        devices, peaks = jax.devices()[:cell.chips], None
    log(f"device: {devices[0].device_kind} x {len(jax.devices())}")

    loop = cells.loop_class(cell.traffic["kind"])(cell.config, cell.traffic,
                                                  int(seed))
    loop.setup()
    log("setup phases: " + ", ".join(f"{k} {v:.3f} s"
                                     for k, v in loop.phases.items()))
    log(loop.described)
    if fault is not None:
        loop.plant(fault)

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    counter = CompileCounter()
    t_window = time.perf_counter()
    setup_s = t_window - t0
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        rec = loop.window(float(seconds), jax.profiler.TraceAnnotation)
    compiles = counter.close()
    rec.peaks = peaks
    if trace:
        jax.profiler.stop_trace()
        try:
            rec.reduction = trace_reduce.reduce_trace(trace_reduce.load(
                trace_reduce.find_xplane(str(TRACE_DIR))))
        except ValueError as e:
            log(f"trace: {e}")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    memory_peak = _memory_peak(devices)
    log(f"window: {rec.attempted} requests in {rec.window_s:.3f} s; "
        f"compiles_in_window={compiles} retraces_in_window={rec.retraces}; "
        f"setup_s={setup_s:.3f}")
    log("answers (graph, program's seconds): "
        + ", ".join(f"{k} {t:.3f}" for k, t in rec.calls))

    for err in loop.errors[:5]:
        log(f"failed answer: {err}")
    checks = loop.check(rec)
    correct = (rec.attempted > 0
               and all(v <= lim for v, lim in checks.values()))
    values = dict(rec.end_to_end, setup_s=setup_s)
    log("end to end: " + json.dumps(values))
    if trace:
        metrics = cells.metric_values(
            cell.per_layer,
            {m["name"]: cells.reader(m["name"])(rec) for m in cell.per_layer})
    else:
        metrics = cells.metric_values(cell.end_to_end, values)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": rec.attempted,
           "failed": rec.failed, "metrics": metrics, "device": device}
    if trace and rec.reduction is not None:
        device["busy_s"] = rec.reduction.busy_s
        device["window_s"] = rec.reduction.window_s
        out["breakdown"] = trace_reduce.breakdown(rec.reduction)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def print_result(out: dict) -> None:
    """The checks as the last lines of standard error, and the result as
    the last line of standard output."""
    sys.stdout.flush()
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
