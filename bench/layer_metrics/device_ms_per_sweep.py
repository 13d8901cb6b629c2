"""Device busy time of the traced window, in ms, divided by the mex sweeps
of the colorings in it (every device op counts, not only the sweeps')."""


def read(rec):
    red = rec.reduction
    sweeps = sum(sum(c["sweeps_per_round"]) for c in rec.colorings)
    if red is None or not sweeps:
        return None
    return 1e3 * red.busy_s / sweeps
