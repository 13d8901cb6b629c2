"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler ships with jaxlib, so these tests run here on the CPU: the
Pallas kernels at real widths (``interpret=False``: Mosaic, not the
interpreter) and the iterative/sort plan program at the scale-20 envelope,
each against one chip of a described ``v5e:2x2`` topology. A compile that
passes says the chip's compiler accepts the program and that it fits the
chip's 16 GiB; it runs nothing.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and it keeps it until
it exits, so every test worker must collect these tests without touching
it. The persistent compilation cache is off around the compiles (a compile
for a described chip can be written to it but never read back).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.api import ColoringSpec, PlanShape, get_strategy
from repro.core.engine import get_backend
from repro.core.frontier import frontier_capacities
from repro.core.graph import DeviceGraph, pad_bucket
from repro.kernels.conflict import conflict_mask
from repro.kernels.firstfit import firstfit
from repro.kernels.round_fused import round_fused

HBM_BYTES = 16 << 30                 # one v5e chip
V20 = 1 << 20                        # scale 20
# RMAT-G at scale 20, edge factor 8: ~15.6M directed edges after dedup;
# the plan envelope rounds up the pad_bucket ladder
SCALE20 = PlanShape(num_vertices=V20, padded_edges=pad_bucket(16 << 20),
                    max_degree=1 << 12)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("words", [2, 8])
@pytest.mark.parametrize("width", [64, 128])
@pytest.mark.parametrize("kernel", ["firstfit", "round_fused"])
def test_kernel_compiles_for_v5e(one_chip, kernel, width, words):
    slab = _sds((V20, width), jnp.int32, one_chip)
    if kernel == "firstfit":
        fn = jax.jit(lambda x: firstfit(x, words=words, interpret=False))
        compiled = fn.lower(slab).compile()
    else:
        fn = jax.jit(lambda x, own: round_fused(x, own, words=words,
                                                interpret=False))
        compiled = fn.lower(slab, _sds((V20,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_binned_slab_and_hub_path_compile_for_v5e(one_chip):
    """A lockstep wavefront step of a binned plan at scale 20: the kernel
    over a ``2^14``-row slab of the bin's width, its bitset sized from the
    bin, and the hub path's named sort over a full ``cap_e`` window."""
    from repro.core.engine import HUB_WIDTH, num_color_words
    from repro.core.mex import segment_mex
    rows, window = 1 << 14, 327680
    words = num_color_words(HUB_WIDTH + 1)
    kernel = jax.jit(lambda x, own: round_fused(x, own, words=words,
                                                interpret=False))
    compiled = kernel.lower(_sds((rows, HUB_WIDTH), jnp.int32, one_chip),
                            _sds((rows,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    hub = jax.jit(lambda v, c: segment_mex(v, c, rows, hub=True))
    e = _sds((window,), jnp.int32, one_chip)
    text = hub.lower(e, e).compile().as_text()
    assert "hub_mex" in text and " sort(" in text


def test_conflict_kernel_compiles_for_v5e(one_chip):
    e = _sds((1 << 24,), jnp.int32, one_chip)
    fn = jax.jit(lambda a, b, c, d: conflict_mask(a, b, c, d,
                                                  interpret=False))
    compiled = fn.lower(e, e, e, e).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_iterative_sort_plan_compiles_at_scale20(one_chip):
    """The program ``compile_plan(ColoringSpec(strategy="iterative",
    engine="sort"), g)`` runs on the chip, for the scale-20 envelope,
    through an abstract DeviceGraph (frontier auxiliary included)."""
    st = SCALE20
    spec = ColoringSpec(strategy="iterative", engine="sort",
                        concurrency=1 << 14, max_rounds=256)
    prog = get_strategy("iterative").device_program(spec,
                                                    get_backend("sort"))
    E, V = st.padded_edges, st.num_vertices
    dg = DeviceGraph(
        num_vertices=V, num_directed_edges=E,
        src=_sds((E,), jnp.int32, one_chip),
        dst=_sds((E,), jnp.int32, one_chip),
        max_degree=st.max_degree, ell_slot=None, ell_width=0,
        inc_ptr=_sds((V + 1,), jnp.int32, one_chip))
    assert frontier_capacities(V, E, st.max_degree)[0] > 0
    compiled = jax.jit(prog).lower(dg).compile()
    text = compiled.as_text()
    assert "sort" in text and "while" in text
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("devices", [1, 4])
def test_distributed_fused_kernel_compiles_for_v5e(topo, one_chip, devices):
    """The distributed strategy's shard_map program with the compiled
    ``fused_pallas`` kernel inside (varying-axes typing on), on one chip
    and on the 2x2 mesh of the described host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.distributed import build_distributed_coloring
    from repro.core.engine import FusedPallasMexBackend
    names = ("x",) if devices == 1 else ("data", "model")
    mesh = Mesh(np.array(topo.devices[:devices]).reshape(
        (1,) if devices == 1 else (2, 2)), names)
    Vl, El, Bl = 1 << 14, 1 << 18, 1 << 11
    fn = build_distributed_coloring(
        mesh, Vl, El, engine=FusedPallasMexBackend(interpret=False),
        max_colors=40, ell_width=39, frontier_cap_v=512,
        frontier_cap_e=1 << 13, wire="boundary", wire_colors=40)
    sh = NamedSharding(mesh, P(names, None))
    with jax.set_mesh(mesh):
        compiled = fn.lower(*(_sds((devices, w), jnp.int32, sh)
                              for w in (El, El, Bl))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert devices == 1 or "all-gather" in text
    assert _device_bytes(compiled) < HBM_BYTES
