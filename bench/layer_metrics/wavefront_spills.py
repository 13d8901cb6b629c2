"""Offset classes per coloring that overflowed the lockstep wavefront's
window and took a full-edge pass instead: the mean over the window's calls
of the ``wavefront_spills`` counter of each ``plan.call`` record
(``repro.core.spans``), which is ``ColoringReport.wavefront_spills``.
``None`` where the program keeps no such counter."""
from bench.plan_spans import window_calls


def read(rec):
    calls = window_calls(rec)
    if calls is None:
        return None
    counts = [getattr(c, "counters", {}).get("wavefront_spills")
              for c in calls]
    if any(n is None for n in counts):
        return None
    return sum(counts) / len(counts)
