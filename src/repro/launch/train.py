"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --steps 300 --batch 8 --seq 512 --ckpt-dir /tmp/ckpt

Features exercised: deterministic data pipeline (skip-to-step on resume),
sharded train step (uses whatever devices exist; production meshes are
exercised by dryrun.py), NaN-step rejection, atomic+async checkpointing,
elastic restart (restore re-shards onto the current mesh).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke_config
from jax import set_mesh
from .. import models
from ..train import (AdamWConfig, init_opt_state, make_train_step, checkpoint,
                     data)
from ..train.train_step import TrainStepConfig
from ..models.config import ShapeConfig
from ..parallel.sharding import rules_for_mesh, activation_rules
from . import specs as S


def build(cfg, opt_cfg, ts_cfg, mesh=None):
    params, axes = models.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params, opt_cfg)
    step = make_train_step(cfg, opt_cfg, ts_cfg)
    if mesh is None or len(jax.devices()) == 1:
        return params, opt_state, jax.jit(step), None
    rules = rules_for_mesh(mesh)
    p_sh = S.tree_shardings(jax.eval_shape(lambda: params), axes, rules, mesh)
    params = jax.tree.map(jax.device_put, params, p_sh)

    def fn(p, o, b):
        with activation_rules(rules):
            return step(p, o, b)

    with set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=(p_sh, None, None),
                         donate_argnums=(0, 1))
    return params, opt_state, jitted, mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)
    ts_cfg = TrainStepConfig(microbatches=args.microbatches)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    dcfg = data.data_config_for(cfg, shape)

    params, opt_state, step_fn, _ = build(cfg, opt_cfg, ts_cfg)

    start = 0
    if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
        tree, start = checkpoint.restore(
            args.ckpt_dir, {"params": params, "opt": opt_state})
        params, opt_state = tree["params"], tree["opt"]
        print(f"[train] resumed from step {start}")

    losses = []
    t0 = time.time()
    pending_ckpt = None
    for s in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 data.batch_for_step(dcfg, s).items()}
        params, opt_state, m = step_fn(params, opt_state, batch)
        losses.append(float(m["loss"]))
        if (s + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            print(f"[train] step {s+1}/{args.steps} loss={losses[-1]:.4f} "
                  f"gnorm={float(m['grad_norm']):.3f} lr={float(m['lr']):.2e} "
                  f"skipped={int(m['skipped'])} {dt:.2f}s/step")
            t0 = time.time()
        if args.ckpt_dir and (s + 1) % args.ckpt_every == 0:
            if pending_ckpt is not None:
                pending_ckpt.join()
            pending_ckpt = checkpoint.save(
                args.ckpt_dir, s + 1, {"params": params, "opt": opt_state},
                async_write=True)
    if pending_ckpt is not None:
        pending_ckpt.join()
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps,
                        {"params": params, "opt": opt_state})
    print(f"[train] done. first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
