"""Mex sweeps per coloring, from each ``ColoringReport.sweeps_per_round``
of the window's colorings (the mean; it repeats exactly for a seed)."""


def read(rec):
    if not rec.colorings:
        return None
    total = sum(sum(c["sweeps_per_round"]) for c in rec.colorings)
    return total / len(rec.colorings)
