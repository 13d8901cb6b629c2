"""Pallas TPU kernel: blocked bitmask first-fit (the paper's Alg. 1 lines 5-6).

The paper's inner loop marks neighbor colors in a ``forbiddenColors`` array
and scans for the smallest free positive color. The TPU translation
(DESIGN.md §2): the irregular neighbor-color *gather* is hoisted outside the
kernel (XLA `take` — HBM-bandwidth bound, vectorized); the kernel consumes a
dense ELL slab of neighbor colors and does the compute-hot part in VMEM:

  * build a per-vertex forbidden **bitmask** (``W = C/32`` int32 words)
    with VPU shift/or ops — the register-resident analogue of
    ``forbiddenColors``;
  * extract the lowest free bit of each word with a count-trailing-zeros
    bit trick and take the first word that has one.

Layout: the kernel runs *slot-major* — the wrapper transposes the [V, D]
slab to [D, V], so vertices sit on the 128 lanes and neighbor slots on the
sublanes. Every per-vertex OR over slots is then an elementwise OR of
8-sublane row groups (no cross-lane reduction), and the per-vertex results
come out lane-dense as one [1, BV] row. Everything is int32: Mosaic lowers
neither unsigned reductions nor a bitwise-or ``lax.reduce``.

Tiling: grid is (vertex tiles × slot tiles). The forbidden mask lives in a
``[W, 8, BV]`` VMEM scratch — eight partial bitsets per word, one per
sublane — and accumulates across the slot (innermost, "arbitrary") grid
dimension; on the last slot tile the eight partials are OR-ed together with
three sublane rotations and the mex is written. ``block_v`` (lanes) is a
multiple of 128 on TPU and ``block_d`` (sublanes) a multiple of 8.

Colors are assumed < 32*W (the greedy bound Δ+1 makes W = ceil((Δ+2)/32)
safe); larger colors fall out of every word and forbid nothing.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INT32_MAX = np.iinfo(np.int32).max
# bit k of the index of an isolated bit b is set iff b & _CTZ_MASKS[k] != 0
_CTZ_MASKS = tuple(int(np.uint32(m).view(np.int32)) for m in
                   (0xAAAAAAAA, 0xCCCCCCCC, 0xF0F0F0F0, 0xFF00FF00,
                    0xFFFF0000))


def init_bitset(forb_ref):
    """Clear the ``[W, 8, BV]`` bitset; color 0 ("uncolored") is always
    forbidden, so word 0 starts with bit 0 set."""
    word = lax.broadcasted_iota(jnp.int32, forb_ref.shape, 0)
    forb_ref[...] = jnp.where(word == 0, 1, 0).astype(jnp.int32)


def or_rows(x):
    """OR the rows of ``x`` [BD, BV] into 8 partial rows [8, BV] (aligned
    8-sublane slices, elementwise ORs only)."""
    acc = x[0:8]
    for k in range(1, x.shape[0] // 8):
        acc = acc | x[8 * k:8 * (k + 1)]
    return acc


def or_sublanes(x):
    """All-reduce OR over the 8 sublanes of ``x`` [8, BV]: every row ends
    up holding the OR of all eight."""
    for shift in (4, 2, 1):
        x = x | pltpu.roll(x, shift, 0)
    return x


def accumulate_bitset(forb_ref, colors, forbid, *, words: int):
    """OR bit ``c & 31`` of every forbidding color ``c`` into word
    ``c >> 5`` of its vertex's bitset. ``colors`` [BD, BV] int32 >= 0,
    ``forbid`` a [BD, BV] mask or ``None`` (every entry forbids)."""
    word_idx = colors >> 5
    bitval = jnp.left_shift(jnp.int32(1), colors & 31)
    if forbid is not None:
        bitval = jnp.where(forbid, bitval, 0)

    def body(w, carry):
        forb_ref[w] = forb_ref[w] | or_rows(jnp.where(word_idx == w, bitval, 0))
        return carry

    lax.fori_loop(0, words, body, 0)


def bitset_mex(forb_ref, *, words: int):
    """Smallest color whose bit is clear, per lane: [8, BV] int32 (all
    rows equal)."""

    def body(w, best):
        free = ~or_sublanes(forb_ref[w])
        low = free & (-free)                     # the lowest free bit alone
        pos = jnp.zeros_like(low)
        for k, mask in enumerate(_CTZ_MASKS):
            pos = pos | jnp.where((low & mask) != 0, 1 << k, 0)
        return jnp.minimum(best, jnp.where(free != 0, w * 32 + pos, _INT32_MAX))

    shape = forb_ref.shape[1:]
    return lax.fori_loop(0, words, body, jnp.full(shape, _INT32_MAX, jnp.int32))


def _firstfit_kernel(nbr_ref, out_ref, forb_ref, *, words: int):
    """One (vertex-tile, slot-tile) grid step.

    nbr_ref:  [BD, BV]    int32 neighbor colors (0 = no neighbor / uncolored)
    out_ref:  [1, BV]     int32 mex output (written on the last slot tile)
    forb_ref: [W, 8, BV]  int32 VMEM scratch, persists across slot tiles
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_bitset(forb_ref)

    accumulate_bitset(forb_ref, nbr_ref[...], None, words=words)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = bitset_mex(forb_ref, words=words)[0:1]


def slot_block(d: int, block_d: int) -> int:
    """Sublane block height for an ELL width ``d``: ``block_d``, or ``d``
    rounded up to the 8-row tile when the whole slab is narrower."""
    if block_d % 8:
        raise ValueError(f"block_d={block_d} must be a multiple of 8")
    return min(block_d, -(-max(d, 1) // 8) * 8)


def to_slot_major(x: jnp.ndarray, vp: int, dp: int) -> jnp.ndarray:
    """[V, D] slab -> zero-padded slot-major [dp, vp] kernel operand."""
    v, d = x.shape
    return jnp.zeros((dp, vp), jnp.int32).at[:d, :v].set(x.astype(jnp.int32).T)


def row_shape(x: jnp.ndarray, vp: int) -> jax.ShapeDtypeStruct:
    """The [1, vp] int32 per-vertex output, varying over the same mesh
    axes as the operand ``x`` (a kernel called under ``shard_map`` must
    say so)."""
    return jax.ShapeDtypeStruct((1, vp), jnp.int32, vma=jax.typeof(x).vma)


def vmem_estimate(*, words: int = 16, block_v: int = 512,
                  block_d: int = 128) -> int:
    """Per-grid-step VMEM footprint (bytes) of :func:`firstfit`'s launch
    geometry, for the analyzer's budget checker (repro.analysis.budgets):
    the [BD, BV] input and [1, BV] output blocks, the ``[W, 8, BV]``
    scratch bitset, and the [BD, BV] per-word contribution intermediate.
    ``words`` scales with the color bound (W = ceil(C/32) ~ max_degree/32),
    which is how a very high-degree plan breaches the budget."""
    blocks = 4 * block_v * (block_d + 1)
    scratch = 4 * words * 8 * block_v
    intermediate = 4 * block_d * block_v
    return blocks + scratch + intermediate


@functools.partial(
    jax.jit, static_argnames=("words", "block_v", "block_d", "interpret")
)
def firstfit(
    nbr_colors: jnp.ndarray,
    *,
    words: int = 16,
    block_v: int = 512,
    block_d: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Minimum excluded positive color per row of an ELL neighbor-color slab.

    nbr_colors: [V, D] int32, entries >= 0; 0 = absent/uncolored, entries
    >= 32*words forbid nothing. Returns mex [V] int32 >= 1. V and D are
    padded internally to the block shape (pad slots contribute color 0,
    which is always forbidden anyway).
    """
    v, d = nbr_colors.shape
    bd = slot_block(d, block_d)
    vp = -(-v // block_v) * block_v
    dp = -(-d // bd) * bd
    x = to_slot_major(nbr_colors, vp, dp)
    out = pl.pallas_call(
        functools.partial(_firstfit_kernel, words=words),
        grid=(vp // block_v, dp // bd),
        in_specs=[pl.BlockSpec((bd, block_v), lambda i, j: (j, i))],
        out_specs=pl.BlockSpec((1, block_v), lambda i, j: (0, i)),
        out_shape=row_shape(x, vp),
        scratch_shapes=[pltpu.VMEM((words, 8, block_v), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x)
    return out[0, :v]
