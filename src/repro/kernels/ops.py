"""Jit'd wrappers wiring the Pallas kernels into the coloring engine.

On a TPU the kernels compile to Mosaic; on the CPU (the test path) they run
with ``interpret=True`` — Pallas executes the kernel body in its
interpreter. :func:`resolve_interpret` picks between the two when a call
leaves ``interpret`` unset, from the backend in use at call time.

The speculative drivers no longer thread kernel closures through here: the
``"ell_pallas"`` entry in :mod:`repro.core.engine` binds the firstfit kernel
to a graph's ELL layout directly (``engine="ell_pallas"``). What remains are
the standalone kernel wrappers (serial-style mex over a slab, conflict
counting) used by benchmarks and tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .firstfit import firstfit


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret=`` override against the default backend as
    it is at call time: compiled (``False``) on a TPU, the Pallas
    interpreter (``True``) on the CPU. Any other backend has no default —
    the kernels are Mosaic TPU kernels — and raises rather than silently
    interpreting; pass ``interpret=True`` explicitly there.

    Every kernel entry point that accepts ``interpret=None`` must call this
    OUTSIDE its jit boundary: ``interpret`` is a static argument, so a
    fallback read inside a jitted body is frozen at first trace — the cache
    key stays ``None`` and a later change of backend silently keeps serving
    the stale trace.

    This is exactly the hazard class the static analyzer lints for as
    RETRACE001 (:mod:`repro.analysis.retrace`): a static jit arg that
    defaults to ``None`` or is tested ``is None`` inside the jitted body.
    The CI lint lane keeps the package free of new instances; this function
    is the sanctioned fix pattern.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels are TPU (Mosaic) kernels and have no compiled "
        f"path on backend {platform!r}; pass interpret=True to run them in "
        "the Pallas interpreter")


def ell_gather_colors(colors: jnp.ndarray, ell: jnp.ndarray) -> jnp.ndarray:
    """Gather neighbor colors for an ELL adjacency slab.

    colors: [V] int32 (0 = uncolored); ell: [V, D] int32 neighbor ids with
    pad = V. Returns [V, D] int32 (pad slots -> 0). The gather stays outside
    the kernel (DESIGN.md §2: regularize, then go fast).
    """
    cpad = jnp.concatenate([colors, jnp.zeros((1,), jnp.int32)])
    return cpad[jnp.minimum(ell, colors.shape[0])]


@functools.partial(jax.jit, static_argnames=("words", "interpret"))
def _ell_mex(colors: jnp.ndarray, ell: jnp.ndarray, *, words: int,
             interpret: bool) -> jnp.ndarray:
    nbr = ell_gather_colors(colors, ell)
    return firstfit(nbr, words=words, interpret=interpret)


def ell_mex(colors: jnp.ndarray, ell: jnp.ndarray, *, words: int = 16,
            interpret: bool | None = None) -> jnp.ndarray:
    """mex per vertex from an ELL slab — kernel-powered Alg. 1 inner loop."""
    return _ell_mex(colors, ell, words=words,
                    interpret=resolve_interpret(interpret))
