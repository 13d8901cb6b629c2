"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import rmat, greedy_color, color_iterative, validate_coloring
from repro.kernels import (firstfit, firstfit_ref, conflict_mask,
                           conflict_mask_ref, ell_mex)


@pytest.mark.parametrize("v,d", [(1, 1), (7, 3), (100, 17), (512, 16),
                                 (777, 33), (1024, 128)])
@pytest.mark.parametrize("cmax", [5, 200, 500])
def test_firstfit_shape_sweep(v, d, cmax):
    rng = np.random.default_rng(v * 1000 + d + cmax)
    nbr = rng.integers(0, cmax, size=(v, d)).astype(np.int32)
    nbr[rng.random((v, d)) < 0.3] = 0
    got = firstfit(jnp.asarray(nbr), words=16, interpret=True)
    want = firstfit_ref(jnp.asarray(nbr), 512)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("blocks", [(64, 32), (256, 64), (512, 128)])
def test_firstfit_block_shapes(blocks):
    bv, bd = blocks
    rng = np.random.default_rng(bv)
    nbr = rng.integers(0, 300, size=(300, 50)).astype(np.int32)
    got = firstfit(jnp.asarray(nbr), words=16, block_v=bv, block_d=bd,
                   interpret=True)
    want = firstfit_ref(jnp.asarray(nbr), 512)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_firstfit_dense_rows():
    """Rows forbidding exactly 1..k force mex = k+1."""
    v, d = 64, 40
    nbr = np.zeros((v, d), np.int32)
    for i in range(v):
        k = i % 33
        nbr[i, :k] = np.arange(1, k + 1)
    got = np.asarray(firstfit(jnp.asarray(nbr), words=16, interpret=True))
    for i in range(v):
        assert got[i] == (i % 33) + 1


@pytest.mark.parametrize("e", [1, 100, 1024, 5000])
def test_conflict_kernel(e):
    rng = np.random.default_rng(e)
    cs = rng.integers(0, 10, e).astype(np.int32)
    cd = rng.integers(0, 10, e).astype(np.int32)
    s = rng.integers(0, 100, e).astype(np.int32)
    t = rng.integers(0, 100, e).astype(np.int32)
    got = conflict_mask(*(jnp.asarray(x) for x in (cs, cd, s, t)), interpret=True)
    want = conflict_mask_ref(*(jnp.asarray(x) for x in (cs, cd, s, t)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ell_mex_against_graph():
    g = rmat.paper_graph("RMAT-G", scale=9, seed=7)
    colors = greedy_color(g).astype(np.int32)
    ell, _ = g.to_ell()
    mex = np.asarray(ell_mex(jnp.asarray(colors), jnp.asarray(ell),
                             interpret=True))
    nbrc = np.where(ell < g.num_vertices,
                    colors[np.minimum(ell, g.num_vertices - 1)], 0)
    assert not np.any(mex[:, None] == np.where(nbrc > 0, nbrc, -1))
    assert np.all(mex <= colors)


def test_iterative_with_kernel_mex_engine():
    """ITERATIVE with the Pallas firstfit engine (engine="ell_pallas", bound
    to the graph's ELL layout) == valid coloring with the same round
    structure as the sort engine."""
    g = rmat.paper_graph("RMAT-ER", scale=8, seed=3)
    dg = g.to_device(layout=("edges", "ell"))
    res_k = color_iterative(dg, concurrency=g.num_vertices,
                            engine="ell_pallas")
    res_s = color_iterative(dg, concurrency=g.num_vertices)
    assert validate_coloring(g, np.asarray(res_k.colors))
    assert res_k.rounds == res_s.rounds
    np.testing.assert_array_equal(np.asarray(res_k.conflicts_per_round),
                                  np.asarray(res_s.conflicts_per_round))


def test_importing_repro_initialises_no_backend():
    """Importing the package decides nothing about the device: no backend
    is initialised until a call needs one (the kernels' interpret mode is
    resolved per call, not at import)."""
    import os
    import subprocess
    import sys
    code = ("import repro, repro.core, repro.kernels, repro.serve, "
            "repro.analysis\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
