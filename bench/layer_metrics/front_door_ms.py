"""Host time of the front door per coloring, in ms: the mean over the
window's calls of the ``plan.call`` span less its ``plan.device`` child
(``repro.core.spans``), which is the host work (canonicalise, layout,
upload, report) during which the front door holds the device idle."""
from bench.plan_spans import mean_ms, window_calls


def read(rec):
    calls = window_calls(rec)
    if calls is None:
        return None
    return mean_ms(calls, lambda c: c.total_ns("plan.call")
                   - c.total_ns("plan.device"))
