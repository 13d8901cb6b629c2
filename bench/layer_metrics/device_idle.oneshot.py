"""Device idle share of the traced window in the one-shot cells, in %
(``trace_reduce.idle_percent``)."""
from bench.trace_reduce import idle_percent


def read(rec):
    return idle_percent(rec.reduction)
