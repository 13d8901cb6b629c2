"""The mex's share of its HBM roofline under a degree-binned ELL layout,
in %: the least time the window's sweeps need at the chip's peak bandwidth
(``roofline.sweep_bytes`` over ``peaks.json``, as ``round_fused_roofline``
counts them), divided by the summed device self time of the ``round_fused``
kernel's ops and the hub path's sort (the ops named ``hub_mex``). The work
is counted the same way whichever path takes a row; with no hub ops it is
``round_fused_roofline``.

The hub path's sort is the only op of that path with a name of its own:
its window slices, its segment-min and the comparisons and gathers around
the sort fuse into unnamed ``fusion.N`` (or ``scatter.N``) ops, which this
reader does not count. So the time it divides by leaves part of the hub
path out, and the share reads high by an amount not measured; attributing
those ops needs a reader of the HLO's ``op_name`` metadata."""
from bench.roofline import sweep_bytes
from bench.trace_reduce import kernel_time

PATHS = ("round_fused", "hub_mex")


def read(rec):
    red = rec.reduction
    if red is None or rec.peaks is None or not rec.colorings:
        return None
    seconds = sum(kernel_time(red, prefix)[0] for prefix in PATHS)
    if seconds <= 0:
        return None
    total = sum(sweep_bytes(
        num_vertices=rec.num_vertices,
        num_directed_edges=c["num_directed_edges"],
        sweeps_per_round=c["sweeps_per_round"],
        conflicts_per_round=c["conflicts_per_round"])
        for c in rec.colorings)
    return 100.0 * total / rec.peaks["hbm_bytes_per_s"] / seconds
