"""Host-to-device copy time per coloring, in ms: the mean over the
window's calls of the ``plan.transfer`` span (``repro.core.spans``), one
``jax.device_put`` of the call's arrays, blocked on until they are on the
device."""
from bench.plan_spans import mean_ms, window_calls


def read(rec):
    calls = window_calls(rec)
    if calls is None:
        return None
    return mean_ms(calls, lambda c: c.total_ns("plan.transfer"))
