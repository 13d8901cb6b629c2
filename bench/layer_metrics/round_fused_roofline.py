"""The ``round_fused`` Pallas kernel's share of its HBM roofline, in %:
the least time the window's sweeps need at the chip's peak bandwidth
(``roofline.sweep_bytes`` over ``peaks.json``), divided by the summed
device self time of the kernel's ops in the trace. Its HLO instructions
carry the kernel's jit name, ``round_fused``. Integer compares are its
only arithmetic, so the share is bounded by bytes."""
from bench.roofline import sweep_bytes
from bench.trace_reduce import kernel_time

KERNEL = "round_fused"


def read(rec):
    red = rec.reduction
    if red is None or rec.peaks is None or not rec.colorings:
        return None
    seconds, _ = kernel_time(red, KERNEL)
    if seconds <= 0:
        return None
    total = sum(sweep_bytes(
        num_vertices=rec.num_vertices,
        num_directed_edges=c["num_directed_edges"],
        sweeps_per_round=c["sweeps_per_round"],
        conflicts_per_round=c["conflicts_per_round"])
        for c in rec.colorings)
    return 100.0 * total / rec.peaks["hbm_bytes_per_s"] / seconds
