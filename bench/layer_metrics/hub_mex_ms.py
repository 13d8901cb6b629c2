"""Device time of the fused engine's per-edge hub path per coloring, in
ms: the summed self time of the window's ops named ``hub_mex`` over the
window's colorings. Those are the hub path's sort alone
(``repro.core.mex.hub_sort``): its window slices, segment-min, comparisons
and gathers fuse into unnamed ``fusion.N`` (or ``scatter.N``) ops, which
this reader does not count, so it reads below the whole path's time.
``None`` where no such op ran."""
from bench.trace_reduce import kernel_time

PREFIX = "hub_mex"


def read(rec):
    red = rec.reduction
    if red is None or not rec.colorings:
        return None
    seconds, count = kernel_time(red, PREFIX)
    if not count:
        return None
    return 1e3 * seconds / len(rec.colorings)
