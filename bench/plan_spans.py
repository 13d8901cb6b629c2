"""The window's coloring calls as the program records them: the
``plan.call`` records of ``repro.core.spans``, read by the front door's
per-layer metrics."""


def window_calls(rec):
    """The last ``rec.attempted`` finished calls, which are the window's:
    the harness calls no plan between the window's end and the readers
    (the check runs the numpy reference alone). ``None`` where the program
    keeps no spans, or the ring holds fewer ``plan.call`` records."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    calls = spans.recent_calls(rec.attempted)
    if (not rec.attempted or len(calls) < rec.attempted
            or any(c.root.name != "plan.call" for c in calls)):
        return None
    return calls


def mean_ms(calls, ns_of) -> float:
    """The mean of ``ns_of(call)`` over ``calls``, in ms."""
    return sum(ns_of(c) for c in calls) / len(calls) / 1e6
