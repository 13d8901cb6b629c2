"""Pallas TPU kernel: edge-parallel conflict detection (Alg. 2, phase 2).

Consumes pre-gathered endpoint colors (the irregular gather is an XLA `take`
outside the kernel, per DESIGN.md §2) plus the endpoint ids, and emits the
per-edge conflict mask ``color[src] == color[dst] and src > dst and colored``
— the exact predicate of Alg. 2 line 13. Pure VPU compare/select work over
128-aligned edge tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conflict_kernel(csrc_ref, cdst_ref, src_ref, dst_ref, out_ref):
    csrc = csrc_ref[...]
    cdst = cdst_ref[...]
    src = src_ref[...]
    dst = dst_ref[...]
    conf = (csrc == cdst) & (csrc > 0) & (src > dst)
    out_ref[...] = conf.astype(jnp.int32)


def vmem_estimate(*, block_e: int = 1024) -> int:
    """Per-grid-step VMEM footprint (bytes) of :func:`conflict_mask` for
    the analyzer's budget checker: four int32 input blocks, one output
    block, and the boolean compare intermediate."""
    return 4 * block_e * 5 + block_e


@functools.partial(jax.jit, static_argnames=("block_e", "interpret"))
def conflict_mask(
    colors_src: jnp.ndarray,
    colors_dst: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    *,
    block_e: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-edge conflict mask [E] int32 (1 = recolor the src endpoint)."""
    (e,) = colors_src.shape
    ep = -(-e // block_e) * block_e

    def pad(x, fill):
        return jnp.full((ep,), fill, jnp.int32).at[:e].set(x.astype(jnp.int32))

    # pad with src == dst so padding never reports a conflict
    args = (pad(colors_src, 0), pad(colors_dst, 0), pad(src, 0), pad(dst, 0))
    grid = (ep // block_e,)
    spec = pl.BlockSpec((block_e,), lambda i: (i,))
    out = pl.pallas_call(
        _conflict_kernel,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((ep,), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return out[:e]
