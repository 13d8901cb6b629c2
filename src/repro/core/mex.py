"""Vectorized segmented first-fit ("mex") — the TPU-native replacement for the
paper's ``forbiddenColors`` stamped array + linear scan (Alg. 1, lines 5-6).

This is the computational core of the ``"sort"`` :class:`~repro.core.engine.
MexBackend` (the registry's layout-free default); the other registered
backends (``"bitmap"``, ``"ell_pallas"``) compute the same function through
different formulations — see ``repro.core.engine`` for the registry and
DESIGN.md §Engine for the parity contract. Drivers go through
``MexBackend.bind(...)``'s returned mex closure; the one exception is the
per-edge path of a binned ELL layout's hub rows (``segment_mex(...,
hub=True)``), which the lockstep wavefront calls on its own hub windows.

Given a multiset of (vertex, forbidden-color) pairs, compute per vertex the
minimum *positive* integer not present. The trick: lexicographically sort the
pairs (two-key ``lax.sort`` — no int64 composite keys, TPU-friendly) and emit
a candidate ``c+1`` wherever a "gap" occurs (next entry belongs to another
vertex, or skips past ``c+1``); the segment-min of candidates is the mex.

Callers must guarantee every live vertex contributes at least one entry
(except under ``hub=True``); the canonical way is to append a synthetic
``(v, 0)`` pair per vertex (color 0 == "uncolored" never collides with real
colors >= 1 and seeds the candidate ``1``) — ``SortMexBackend.bind`` does
exactly this.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

try:  # private: a JAX without it sorts the hub path under the plain name
    from jax._src.lax.lax import _sort_lower
except ImportError:  # pragma: no cover - depends on the installed JAX
    _sort_lower = None

_INT32_MAX = jnp.iinfo(jnp.int32).max


def segment_mex(vertex: jnp.ndarray, color: jnp.ndarray, num_vertices: int,
                *, hub: bool = False) -> jnp.ndarray:
    """Per-vertex minimum excluded positive color.

    vertex: [M] int32 ids in [0, num_vertices]; id == num_vertices is inert
        padding (its segment is computed then discarded).
    color:  [M] int32 >= 0 forbidden colors.
    Returns [num_vertices] int32 mex (>= 1) — garbage for vertices with no
    entries (callers append synthetic (v, 0) entries to avoid that).

    hub: the per-edge path of the binned ``fused_pallas`` engine's hub
    rows, which appends no synthetic entries: a vertex with no entries
    reads 1 (so a row's mex is the larger of the slab's and this path's,
    the path that does not take the row reading 1), and the sort is
    :func:`hub_sort`, named ``hub_mex`` in a device trace.
    """
    ops = (vertex.astype(jnp.int32), color.astype(jnp.int32))
    v_s, c_s = hub_sort(ops) if hub else lax.sort(ops, num_keys=2)
    next_v = jnp.concatenate([v_s[1:], jnp.full((1,), num_vertices + 1, jnp.int32)])
    next_c = jnp.concatenate([c_s[1:], jnp.zeros((1,), jnp.int32)])
    seg_end = next_v != v_s
    gap = seg_end | (next_c > c_s + 1)
    cand = jnp.where(gap, c_s + 1, _INT32_MAX)
    if hub:
        # what a synthetic (v, 0) entry would add: a vertex whose smallest
        # forbidden color is 2 or more is free at 1
        prev_v = jnp.concatenate([jnp.full((1,), -1, jnp.int32), v_s[:-1]])
        cand = jnp.where((prev_v != v_s) & (c_s > 1), 1, cand)
    mex = jax.ops.segment_min(cand, v_s, num_segments=num_vertices + 1)
    if hub:  # an empty segment reads the identity of min
        mex = jnp.where(mex == _INT32_MAX, 1, mex)
    return mex[:num_vertices]


# The hub path's sort as a primitive of its own. XLA names an instruction
# after the primitive that emitted it, so this sort shows in a device trace
# as ``hub_mex.N`` where a ``lax.sort`` shows as ``sort.N`` like every other
# sort of the program; the benchmark's ``hub_mex_ms`` reads it by that name.
# It lowers and batches exactly as ``lax.sort``. Its lowering is JAX's own
# private ``_sort_lower`` (checked by tests/test_tpu_compile.py); where the
# installed JAX lacks it, ``hub_sort`` is ``lax.sort`` and the name is lost.
_hub_sort_p = Primitive("hub_mex")
_hub_sort_p.multiple_results = True
_hub_sort_p.def_abstract_eval(lambda *avals, **params: list(avals))
_hub_sort_p.def_impl(lambda *ops, dimension, is_stable, num_keys: lax.sort(
    ops, dimension=dimension, is_stable=is_stable, num_keys=num_keys))
if _sort_lower is not None:
    mlir.register_lowering(_hub_sort_p, _sort_lower)


def _hub_sort_batch(args, dims, *, dimension, is_stable, num_keys):
    size = next(a.shape[d] for a, d in zip(args, dims) if d is not None)
    args = [batching.bdim_at_front(a, d, size) for a, d in zip(args, dims)]
    out = _hub_sort_p.bind(*args, dimension=dimension + 1,
                           is_stable=is_stable, num_keys=num_keys)
    return out, [0] * len(out)


batching.primitive_batchers[_hub_sort_p] = _hub_sort_batch


def hub_sort(operands):
    """``lax.sort(operands, num_keys=len(operands))`` under the name
    ``hub_mex``, where the installed JAX allows it."""
    if _sort_lower is None:
        return lax.sort(operands, num_keys=len(operands))
    return _hub_sort_p.bind(*operands, dimension=0, is_stable=False,
                            num_keys=len(operands))
