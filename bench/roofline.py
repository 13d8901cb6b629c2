"""The peaks table and the work of a coloring, counted from the algorithm.

``peaks(kind)`` reads ``peaks.json`` (keyed by JAX's ``device_kind``); a
kind that is not in the table is an error, never a default.

``sweep_bytes`` counts the least HBM traffic the coloring's sweeps need,
from the coloring's own record (rounds, sweeps, pending sets) and the
graph's size, never from the padded shapes of an implementation: a PR
that shrinks padding lowers the measured time but not these bytes.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

# int32 colors, flags and neighbour entries
WORD = 4


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``device_kind``)."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS_FILE.name} "
                       f"(known: {sorted(table)}); add its published peaks")
    return table[kind]


def sweep_bytes(*, num_vertices: int, num_directed_edges: int,
                sweeps_per_round: Sequence[int],
                conflicts_per_round: Sequence[int]) -> float:
    """Least bytes that one cold-start coloring's mex sweeps move.

    A sweep over a vertex set S reads each neighbour colour once and each
    vertex's own colour once, and writes its mex and its conflict flag:
    ``4 B * (sum_{v in S} deg(v) + 3 |S|)``. Round 0 sweeps all of V
    (degree sum = the directed edge count). Round r >= 1 sweeps the
    vertices that round r-1 queued (``conflicts_per_round[r-1]``); their
    degree sum is not recorded, so it is taken as ``|S|`` times the mean
    degree. That approximation undercounts when conflicts gather on hubs,
    which can only lower the share, never raise it."""
    mean_degree = num_directed_edges / max(1, num_vertices)
    total = 0.0
    for r, sweeps in enumerate(sweeps_per_round):
        if r == 0:
            size, degsum = num_vertices, float(num_directed_edges)
        else:
            size = int(conflicts_per_round[r - 1])
            degsum = size * mean_degree
        total += int(sweeps) * WORD * (degsum + 3 * size)
    return total
