"""Pallas TPU kernel: ONE fused speculation round — detect→mex→assign in a
single launch over the frontier slab (ROADMAP item 2, ISSUE 6 tentpole).

The paper's iterative algorithm (Alg. 1 + 2) spends every round in three
separate memory passes over the neighborhood data:

  1. *detect*  — gather endpoint colors, test ``c[u] == c[v] and u > v``
                 (Alg. 2 line 13, :mod:`repro.kernels.conflict`);
  2. *mex*     — gather neighbor colors again, build ``forbiddenColors``,
                 scan for the minimum free color (Alg. 1 lines 5-6,
                 :mod:`repro.kernels.firstfit`);
  3. *assign*  — write the new colors back.

On every system the paper studies the round is bandwidth-bound, not
compute-bound, so the pass count IS the round cost. This kernel fuses all
three into one launch in the spirit of Rokos et al.'s atomic-free
detect-and-recolor (arXiv:1505.04086): per vertex tile of the (compacted)
ELL slab it

  * builds the per-row forbidden-color **bitset** in VMEM scratch (the
    ``firstfit.py`` word-mask idiom — ``W = C/32`` int32 words,
    accumulated across neighbor-slot tiles, slot-major so vertices sit
    on the lanes);
  * applies the Alg. 2 conflict predicate against the row's own color in
    the same slab read;
  * emits the mex (the row's next color) and the per-row conflict flag on
    the last slot tile — one read of the ELL slab per round instead of
    three (the benchmark's ``round_fused_roofline`` measures it on the
    chip).

The gather stays OUTSIDE the kernel (DESIGN.md §2 / §FusedRound:
"regularize, then go fast"): neighbor colors arrive as a pre-gathered,
pre-packed ELL block. Each int32 slab entry packs the neighbor's color
with two predicate bits:

  * ``FORBID``  (bit 28) — the entry contributes to the forbidden bitset
    (the ``SweepSpec`` precedence mask, applied at pack time);
  * ``CONFLICT`` (bit 29) — the entry is conflict-eligible: its endpoint
    is pending and ranks below the row (``u > v``), so an equal color
    queues the row for recoloring.

Entries without either bit (slab padding, masked-out edges) are inert:
color 0 is always forbidden by construction, exactly as in ``firstfit``.

The bound is per bin: a row of at most ``D`` entries has its mex at most
``D + 1``, so ``32*words >= D + 2`` for the slab's width ``D`` holds every
mex a row can take. Neighbour colors at or above ``32*words`` drop from the
bitset just like the bitmap backend's out-of-range scatters — they can
never lower a mex that provably stays in range. Under a binned layout
(``repro.core.engine.HUB_WIDTH``) the slab holds only the rows of degree
at most its width, and the words come from that width, not from the
graph's max degree; longer rows take the per-edge hub path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .firstfit import (accumulate_bitset, bitset_mex, init_bitset, or_rows,
                       or_sublanes, row_shape, slot_block,
                       to_slot_major)

# packed-entry layout: bits 0..27 color, bit 28 forbid, bit 29 conflict
COLOR_MASK = (1 << 28) - 1
FORBID_BIT = 1 << 28
CONFLICT_BIT = 1 << 29


def pack_entries(colors: jnp.ndarray, forbid: jnp.ndarray,
                 conflict: jnp.ndarray) -> jnp.ndarray:
    """Pack an ELL block of neighbor colors + predicate masks into the
    kernel's int32 entry format. ``colors`` int32 (values < 2^28),
    ``forbid``/``conflict`` broadcastable booleans."""
    colors = colors.astype(jnp.int32) & COLOR_MASK
    return (colors
            | jnp.where(forbid, jnp.int32(FORBID_BIT), jnp.int32(0))
            | jnp.where(conflict, jnp.int32(CONFLICT_BIT), jnp.int32(0)))


def _round_fused_kernel(ent_ref, own_ref, mex_ref, conf_ref, forb_ref,
                        hit_ref, *, words: int):
    """One (vertex-tile, slot-tile) grid step, slot-major like ``firstfit``.

    ent_ref:  [BD, BV]    int32 packed entries (color | FORBID? | CONFLICT?)
    own_ref:  [1, BV]     int32 the row's current color (conflict operand)
    mex_ref:  [1, BV]     int32 mex output (written on the last slot tile)
    conf_ref: [1, BV]     int32 conflict flag output (last slot tile)
    forb_ref: [W, 8, BV]  int32 VMEM scratch, persists across slot tiles
    hit_ref:  [8, BV]     int32 VMEM scratch: partial conflict flags
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_bitset(forb_ref)
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.int32)

    ent = ent_ref[...]                                     # [BD, BV] int32
    color = ent & COLOR_MASK

    # --- detect: Alg. 2 line 13 against the row's own color -------------
    own = own_ref[...]                                     # [1, BV]
    hit = ((ent & CONFLICT_BIT) != 0) & (color == own) & (own > 0)
    hit_ref[...] = hit_ref[...] | or_rows(hit.astype(jnp.int32))

    # --- mex part 1: accumulate the forbidden bitset (firstfit idiom) ---
    accumulate_bitset(forb_ref, color, (ent & FORBID_BIT) != 0, words=words)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # mex part 2: lowest free bit across the words; conflict flag
        mex_ref[...] = bitset_mex(forb_ref, words=words)[0:1]
        conf_ref[...] = or_sublanes(hit_ref[...])[0:1]


def vmem_estimate(*, words: int = 16, block_v: int = 512,
                  block_d: int = 128) -> int:
    """Per-grid-step VMEM footprint (bytes) of :func:`round_fused`'s launch
    geometry, for the analyzer's budget checker (repro.analysis.budgets):
    the [BD, BV] packed-entry and [1, BV] own-color input blocks, the two
    [1, BV] output blocks, the ``[W, 8, BV]`` bitset and ``[8, BV]`` hit
    scratch, and the [BD, BV] per-word contribution intermediate (same
    geometry as ``firstfit.vmem_estimate``)."""
    blocks = 4 * block_v * (block_d + 3)
    scratch = 4 * (words + 1) * 8 * block_v
    intermediate = 4 * block_d * block_v
    return blocks + scratch + intermediate


@functools.partial(
    jax.jit, static_argnames=("words", "block_v", "block_d", "interpret")
)
def round_fused(
    entries: jnp.ndarray,
    own_colors: jnp.ndarray,
    *,
    words: int = 16,
    block_v: int = 512,
    block_d: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused detect→mex pass over a packed ELL slab.

    entries:    [V, D] int32 packed (:func:`pack_entries`); rows are slab
                rows (compacted frontier rows, or whole-graph vertices).
    own_colors: [V] int32, each row's current color (0 = uncolored — such
                rows never report a conflict).

    Returns ``(mex, conflict)``: mex [V] int32 >= 1 (the smallest positive
    color absent from the row's FORBID entries) and conflict [V] int32
    (1 iff some CONFLICT-eligible entry matches the row's own color).
    The caller applies *assign* as ``where(recolor, mex, own)`` — for the
    speculation inner loop ``recolor = pending`` (fixpoint sweeps); for a
    Rokos detect-and-recolor round ``recolor = conflict``. V and D are
    padded internally to the block shape (pad entries are inert).
    """
    v, d = entries.shape
    bd = slot_block(d, block_d)
    vp = -(-v // block_v) * block_v
    dp = -(-d // bd) * bd
    x = to_slot_major(entries, vp, dp)
    own = jnp.zeros((1, vp), jnp.int32).at[0, :v].set(own_colors)
    row = pl.BlockSpec((1, block_v), lambda i, j: (0, i))
    mex, conf = pl.pallas_call(
        functools.partial(_round_fused_kernel, words=words),
        grid=(vp // block_v, dp // bd),
        in_specs=[pl.BlockSpec((bd, block_v), lambda i, j: (j, i)), row],
        out_specs=[row, row],
        out_shape=[row_shape(x, vp)] * 2,
        scratch_shapes=[
            pltpu.VMEM((words, 8, block_v), jnp.int32),
            pltpu.VMEM((8, block_v), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, own)
    return mex[0, :v], conf[0, :v]


def tile_conflict_counts(conflict: jnp.ndarray,
                         block_v: int = 512) -> jnp.ndarray:
    """Per-vertex-tile conflict counts from the kernel's per-row flags —
    the (padded) sum over each ``block_v`` tile of the launch grid."""
    v = conflict.shape[0]
    vp = -(-v // block_v) * block_v
    padded = jnp.zeros((vp,), jnp.int32).at[:v].set(conflict)
    return padded.reshape(vp // block_v, block_v).sum(axis=1)


def round_fused_ref(entries: jnp.ndarray,
                    own_colors: jnp.ndarray,
                    *, words: int = 16) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-jnp oracle for :func:`round_fused` (tests)."""
    color = entries & COLOR_MASK
    forbid = (entries & FORBID_BIT) != 0
    elig = (entries & CONFLICT_BIT) != 0
    C = 32 * words
    v = entries.shape[0]
    rows = jnp.repeat(jnp.arange(v, dtype=jnp.int32), entries.shape[1])
    key_c = jnp.where(forbid, color, 0).reshape(-1)
    forb = (jnp.zeros((v, C), jnp.uint8)
            .at[rows, jnp.minimum(key_c, C - 1)]
            .set(jnp.where(key_c < C, 1, 0).astype(jnp.uint8).reshape(-1)))
    forb = forb.at[:, 0].set(1)
    value = jnp.arange(C, dtype=jnp.int32)[None, :]
    mex = jnp.where(forb == 0, value,
                    jnp.iinfo(jnp.int32).max).min(axis=1).astype(jnp.int32)
    own = own_colors.astype(jnp.int32)
    conf = (elig & (color == own[:, None])
            & (own[:, None] > 0)).any(axis=1).astype(jnp.int32)
    return mex, conf
