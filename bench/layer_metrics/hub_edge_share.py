"""Share of a coloring's forbidding-edge entries that the fused engine's
per-edge hub path takes, in %: the mean over the window's ``plan.call``
records (``repro.core.spans``) of the ``hub_edges`` counter over the
coloring's directed edge entries. ``hub_edges`` is
``ColoringReport.hub_edges``: the directed entries of the rows too long for
a binned ELL slab. Each directed entry (u, v) is the entry through which
v's color forbids u's, so the denominator counts every forbidding-edge
entry a full sweep reads; later rounds read fewer, in the same rows. The
counter is fixed by the graph and the bin, counted on the host as the
layout is built. ``None`` where the program keeps no such counter."""
from bench.plan_spans import window_calls


def read(rec):
    calls = window_calls(rec)
    if calls is None or len(calls) != len(rec.colorings):
        return None
    hub = [getattr(c, "counters", {}).get("hub_edges") for c in calls]
    if any(n is None for n in hub):
        return None
    return 100.0 * sum(n / c["num_directed_edges"]
                       for n, c in zip(hub, rec.colorings)) / len(hub)
