"""The chip benchmark of the coloring system: ``python bench/run.py``.

Everything that measures lives here: the graph generator, the traffic
loops, the plain reference that decides ``correct``, the trace reduction,
the peaks table and one reader per per-layer metric. ``BENCHMARK.json`` at
the checkout root names the cells; each configuration, traffic mix and
per-layer metric is a file of its own, found by its name.
"""
