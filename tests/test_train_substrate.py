"""Optimizer, checkpoint, data pipeline, compression — substrate tests."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.train import (AdamWConfig, init_opt_state, adamw_update,
                         make_train_step, checkpoint, data)
from repro.train.optimizer import schedule, global_norm
from repro.configs import get_smoke_config
from repro import models


def test_adamw_decreases_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                      weight_decay=0.0, grad_clip=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params, cfg)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw_update(cfg, grads, params, state)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_adamw_skips_nonfinite():
    cfg = AdamWConfig(lr=0.1)
    params = {"w": jnp.ones(3)}
    state = init_opt_state(params, cfg)
    grads = {"w": jnp.asarray([jnp.nan, 1.0, 1.0])}
    p2, s2, m = adamw_update(cfg, grads, params, state)
    assert int(m["skipped"]) == 1
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.ones(3))
    assert int(s2["step"]) == 0  # step not consumed


def test_schedule_warmup_cosine():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in [0, 9, 55, 99, 200]]
    assert lrs[0] < 0.2
    assert abs(lrs[1] - 1.0) < 0.01
    assert 0.1 <= lrs[3] < 0.2
    assert abs(lrs[4] - 0.1) < 1e-6


def test_bf16_moments():
    cfg = AdamWConfig(moment_dtype="bfloat16")
    params = {"w": jnp.ones((4, 4))}
    state = init_opt_state(params, cfg)
    assert state["m"]["w"].dtype == jnp.bfloat16
    p2, s2, _ = adamw_update(cfg, {"w": jnp.ones((4, 4))}, params, state)
    assert s2["v"]["w"].dtype == jnp.bfloat16
    assert p2["w"].dtype == params["w"].dtype


def test_grad_accumulation_equivalence():
    """microbatches=4 == full batch (same grads up to fp tolerance)."""
    from repro.train import TrainStepConfig
    cfg = get_smoke_config("starcoder2-3b")
    params, _ = models.init_params(cfg, jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=0.0, weight_decay=0.0)  # lr 0: compare metrics only
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 17)), jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    s1 = make_train_step(cfg, opt_cfg, TrainStepConfig(microbatches=1))
    s4 = make_train_step(cfg, opt_cfg, TrainStepConfig(microbatches=4))
    opt = init_opt_state(params, opt_cfg)
    _, _, m1 = jax.jit(s1)(params, opt, batch)
    _, _, m4 = jax.jit(s4)(params, opt, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 5e-3
    assert abs(float(m1["grad_norm"]) - float(m4["grad_norm"])) / float(m1["grad_norm"]) < 0.05


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4), jnp.bfloat16),
                                         "d": jnp.asarray(7)}}
    checkpoint.save(str(tmp_path), 5, tree)
    got, step = checkpoint.restore(str(tmp_path), tree)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(got["a"]), np.arange(10.0))
    assert got["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_latest_and_prune(tmp_path):
    tree = {"x": jnp.zeros(2)}
    for s in [1, 2, 3, 4, 5]:
        checkpoint.save(str(tmp_path), s, tree, keep=2)
    assert checkpoint.latest_step(str(tmp_path)) == 5
    assert checkpoint.all_steps(str(tmp_path)) == [4, 5]


def test_checkpoint_detects_corruption(tmp_path):
    tree = {"x": jnp.arange(4.0)}
    checkpoint.save(str(tmp_path), 1, tree)
    # corrupt the array file
    d = checkpoint.step_dir(str(tmp_path), 1)
    path = os.path.join(d, "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(-3, 2)
        f.write(b"zzz")
    with pytest.raises(Exception):
        checkpoint.restore(str(tmp_path), tree)


def test_checkpoint_async(tmp_path):
    tree = {"x": jnp.arange(100.0)}
    t = checkpoint.save(str(tmp_path), 9, tree, async_write=True)
    t.join(timeout=30)
    got, step = checkpoint.restore(str(tmp_path), tree)
    assert step == 9


def test_checkpoint_reshard_on_restore(tmp_path):
    """Elastic restart: restore with explicit shardings places leaves."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    checkpoint.save(str(tmp_path), 2, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = checkpoint.restore(str(tmp_path), tree, shardings=sh)
    assert got["w"].sharding == sh["w"]


# ------------------------------------------------------------------ data
def test_data_deterministic_and_skewed():
    cfg = data.DataConfig(vocab_size=1000, seq_len=64, global_batch=8, seed=3)
    b1 = data.batch_for_step(cfg, 7)
    b2 = data.batch_for_step(cfg, 7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = data.batch_for_step(cfg, 8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted views of the same stream
    assert b1["tokens"].shape == (8, 64)
    # zipf skew: low token ids dominate
    assert (b1["tokens"] < 100).mean() > 0.5


def test_data_host_sharding_partition():
    cfg = data.DataConfig(vocab_size=1000, seq_len=16, global_batch=8, seed=3)
    full = [data.batch_for_step(cfg, 1, host=h, hosts=4)["tokens"] for h in range(4)]
    assert all(f.shape == (2, 16) for f in full)
    # hosts see different data
    assert not np.array_equal(full[0], full[1])


def test_prefetch_waves_conflict_free():
    src = [0, 0, 0, 1, 1, 2, 3, 3, 3, 3]
    waves = data.plan_prefetch_waves(src)
    seen = []
    for w in waves:
        wave_srcs = [src[i] for i in w]
        assert len(set(wave_srcs)) == len(wave_srcs), "source contention"
        seen += w
    assert sorted(seen) == list(range(len(src)))
    assert len(waves) == 4  # max source multiplicity


# ------------------------------------------------------------ compression
def test_compressed_psum_close_to_exact():
    from jax import shard_map
    from repro.parallel.compression import compressed_psum
    import jax
    # single-device psum via shard_map over a trivial mesh
    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(128,)), jnp.float32)

    def f(x):
        return compressed_psum(x, "d", jax.random.PRNGKey(0))

    y = jax.jit(shard_map(f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                          out_specs=jax.sharding.PartitionSpec()))(x)
    err = np.abs(np.asarray(y) - np.asarray(x)).max()
    scale = np.abs(np.asarray(x)).max() / 127
    assert err <= 1.01 * scale  # one quantization step
