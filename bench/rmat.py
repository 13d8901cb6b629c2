"""R-MAT graphs for the benchmark (Chakrabarti, Zhan & Faloutsos, SDM 2004).

The benchmark's own generator, so that a rewrite of the program's generator
cannot move the yardstick. The distribution is the one the paper colors
(arXiv:1205.3809 §4.1): ``edge_factor * 2**scale`` raw pairs, each placed
by ``scale`` independent quadrant choices with probabilities
``(a, b, c, d)``; vertex ids are then shuffled (§5.1).

The whole graph is made on the device in one jitted call from the seed
(:func:`generate`): the pairs are sampled one level at a time in int32,
shuffled, and then deduplicated and symmetrised into the CSR the program's
``Graph`` holds, by two sorts. Shuffling before the deduplication gives the
same graph as "build, then relabel", since relabelling commutes with it.
Only the results come back to the host: the raw pairs (for the plain
reference) and the CSR.

Every random stream is the key of ``(seed, stream)``: the same seed gives
the same graph on any backend, and the streams never overlap.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# stream ids under one seed
GRAPH_STREAM = 1
SHUFFLE_STREAM = 2
ORDER_STREAM = 3


def seed_words(seed: int, stream: int, index: int = 0) -> np.ndarray:
    """``(seed, stream, index)`` as the uint32 words a key is folded
    from; any whole number of up to 64 bits is a seed."""
    seed = int(seed) % (1 << 64)
    return np.array([seed & 0xFFFFFFFF, seed >> 32, int(stream), int(index)],
                    np.uint32)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A host random stream of ``(seed, stream)``, for a pool's order."""
    return np.random.default_rng(np.random.SeedSequence(
        seed_words(seed, stream).tolist()))


def _key(words):
    key = jax.random.key(0)
    for i in range(words.shape[0]):
        key = jax.random.fold_in(key, words[i])
    return key


def _sample(key, scale: int, count: int, params: Tuple[float, ...]):
    """``count`` raw pairs before any shuffle: int32 ``(src, dst)``."""
    a, b, c, _ = params

    def level(i, state):
        src, dst = state
        u = jax.random.uniform(jax.random.fold_in(key, i), (count,),
                               jnp.float32)
        # quadrant a: (0, 0); b: (0, 1); c: (1, 0); d: (1, 1)
        row = (u >= a + b).astype(jnp.int32)
        col = (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(jnp.int32)
        return (src << 1) | row, (dst << 1) | col

    zero = jnp.zeros((count,), jnp.int32)
    return lax.fori_loop(0, scale, level, (zero, zero))


def _check_params(params: Sequence[float]) -> Tuple[float, ...]:
    params = tuple(float(p) for p in params)
    if len(params) != 4 or not np.isclose(sum(params), 1.0):
        raise ValueError(f"R-MAT parameters must be four that sum to 1, "
                         f"got {params}")
    return params


@functools.partial(jax.jit,
                   static_argnames=("scale", "count", "params"))
def _pairs(words, *, scale: int, count: int, params: Tuple[float, ...]):
    src, dst = _sample(_key(words), scale, count, params)
    return jnp.stack([src, dst], 1)


def sample_pairs(seed: int, stream: int, index: int = 0, *, scale: int,
                 count: int, params: Sequence[float]) -> np.ndarray:
    """``count`` raw R-MAT pairs of ``(seed, stream, index)`` as an int32
    ``[count, 2]`` host array, ids in ``[0, 2**scale)`` before any
    shuffle."""
    return np.asarray(_pairs(seed_words(seed, stream, index),
                             scale=int(scale),
                             count=int(count),
                             params=_check_params(params)))


@functools.partial(jax.jit,
                   static_argnames=("scale", "count", "params", "shuffle"))
def _generate(graph_words, shuffle_words, *, scale: int, count: int,
              params: Tuple[float, ...], shuffle: bool):
    V = 1 << scale
    src, dst = _sample(_key(graph_words), scale, count, params)
    perm = (jax.random.permutation(_key(shuffle_words), V).astype(jnp.int32)
            if shuffle else jnp.arange(V, dtype=jnp.int32))
    src, dst = perm[src], perm[dst]
    # undirected keys (lo, hi); self loops sort last, under lo = V
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo = jnp.where(lo == hi, V, lo)
    lo, hi = lax.sort((lo, hi), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = first & (lo < V)
    # both directions of every kept edge, sorted by (row, column); the
    # rest sorts last, under row = V
    rows = jnp.concatenate([jnp.where(keep, lo, V), jnp.where(keep, hi, V)])
    cols = jnp.concatenate([hi, lo])
    rows, cols = lax.sort((rows, cols), num_keys=2)
    row_ptr = jnp.searchsorted(rows, jnp.arange(V + 1, dtype=jnp.int32))
    return jnp.stack([src, dst], 1), perm, row_ptr, cols


class Generated(NamedTuple):
    pairs: np.ndarray    # [count, 2] int32 raw pairs, shuffled
    perm: np.ndarray     # [V] int32: new id of raw vertex i
    graph: object        # the program's host Graph (CSR)


def generate(seed: int, *, scale: int, edge_factor: int,
             rmat: Sequence[float], shuffle: bool = True) -> Generated:
    """The graph of ``seed``: its ``edge_factor * 2**scale`` raw pairs
    (relabelled by the shuffle; duplicates and self loops still in), the
    shuffle, and the deduplicated symmetric CSR as the program's
    ``Graph``, all made in one jitted call."""
    from repro.core.graph import Graph
    scale = int(scale)
    pairs, perm, row_ptr, cols = _generate(
        seed_words(seed, GRAPH_STREAM), seed_words(seed, SHUFFLE_STREAM),
        scale=scale, count=int(edge_factor) << scale,
        params=_check_params(rmat), shuffle=bool(shuffle))
    row_ptr = np.asarray(row_ptr).astype(np.int64)
    col_idx = np.asarray(cols)[: int(row_ptr[-1])]
    graph = Graph(1 << scale, row_ptr, np.ascontiguousarray(col_idx))
    return Generated(np.asarray(pairs), np.asarray(perm), graph)
