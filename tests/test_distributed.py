"""Distributed coloring + sharding tests. Multi-device cases run in a
subprocess with XLA_FLAGS host-device override so the main pytest process
keeps a single device."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_coloring_valid_8dev():
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import rmat, color_distributed, validate_coloring, greedy_color
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        out = {}
        for name in ["RMAT-ER", "RMAT-B"]:
            g = rmat.paper_graph(name, scale=10, seed=3)
            colors, rounds, conf = color_distributed(g, mesh)
            out[name] = dict(valid=bool(validate_coloring(g, colors)),
                             colors=int(colors.max()),
                             serial=int(greedy_color(g).max()),
                             rounds=int(rounds),
                             conflicts=[int(c) for c in conf[:rounds]])
        print(json.dumps(out))
    """))
    for name, r in res.items():
        assert r["valid"], name
        assert r["colors"] <= r["serial"] + 4
        assert r["rounds"] <= 12
        # conflicts decay monotonically-ish; last round zero
        assert r["conflicts"][-1] == 0


def test_distributed_engine_parity():
    """color_distributed accepts every registered mex backend and produces
    identical colors (the backends compute the same mex function)."""
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import rmat, color_distributed, validate_coloring
        g = rmat.paper_graph("RMAT-G", scale=8, seed=5)
        mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
        out = {}
        ref = None
        for engine in ["sort", "bitmap", "ell_pallas"]:
            colors, rounds, _ = color_distributed(g, mesh, engine=engine)
            if ref is None:
                ref = colors
            out[engine] = dict(valid=bool(validate_coloring(g, colors)),
                               rounds=int(rounds),
                               same=bool(np.array_equal(colors, ref)))
        print(json.dumps(out))
    """), devices=2)
    for engine, r in res.items():
        assert r["valid"] and r["same"], (engine, r)


def test_distributed_d2_and_pd2_models():
    """model="d2"/"pd2" through the BSP driver: the constraint-graph
    lowering feeds the same machinery (two-hop halos ride the existing
    full-vector gather), and results validate against the host oracles."""
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import (rmat, color_distributed, BipartiteGraph,
                                validate_d2_coloring, validate_pd2_coloring,
                                greedy_color_d2, greedy_color_pd2)
        mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
        g = rmat.paper_graph("RMAT-G", scale=8, seed=2)
        colors, rounds, conf = color_distributed(g, mesh, model="d2")
        out = dict(d2=dict(valid=bool(validate_d2_coloring(g, colors)),
                           colors=int(colors.max()),
                           serial=int(greedy_color_d2(g).max()),
                           rounds=int(rounds)))
        rng = np.random.default_rng(0)
        edges = np.stack([rng.integers(0, 96, 600),
                          rng.integers(0, 64, 600)], 1)
        bg = BipartiteGraph.from_edges(96, 64, edges)
        colors, rounds, conf = color_distributed(bg, mesh, model="pd2")
        out["pd2"] = dict(valid=bool(validate_pd2_coloring(bg, colors)),
                          n=int(colors.shape[0]),
                          colors=int(colors.max()),
                          serial=int(greedy_color_pd2(bg).max()),
                          rounds=int(rounds))
        print(json.dumps(out))
    """), devices=4)
    assert res["d2"]["valid"] and res["pd2"]["valid"]
    assert res["pd2"]["n"] == 96  # colors only the left class
    # speculative quality stays near the serial oracle, as in the D1 case
    assert res["d2"]["colors"] <= int(1.3 * res["d2"]["serial"]) + 4
    assert res["pd2"]["colors"] <= int(1.3 * res["pd2"]["serial"]) + 4


def test_distributed_matches_across_device_counts():
    """BSP coloring stays valid at different mesh sizes (elastic)."""
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax
        from jax.sharding import Mesh
        from repro.core import rmat, color_distributed, validate_coloring
        g = rmat.paper_graph("RMAT-G", scale=9, seed=1)
        out = {}
        for d in [2, 4, 8]:
            mesh = Mesh(np.array(jax.devices()[:d]), ("x",))
            colors, rounds, _ = color_distributed(g, mesh)
            out[str(d)] = dict(valid=bool(validate_coloring(g, colors)),
                               rounds=int(rounds))
        print(json.dumps(out))
    """))
    assert all(v["valid"] for v in res.values())


def test_sharded_train_step_2x2():
    """Sharded train step on a 2x2 host mesh: loss finite, params update,
    and the result matches the single-device step."""
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro import models
        from repro.train import AdamWConfig, init_opt_state, make_train_step
        from repro.parallel.sharding import (DEFAULT_RULES, rules_for_mesh,
                                             activation_rules)
        from repro.launch import specs as S

        cfg = get_smoke_config("qwen3-4b")
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        rules = rules_for_mesh(mesh)
        params, axes = models.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33)), jnp.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1)
        opt = init_opt_state(params, opt_cfg)
        step = make_train_step(cfg, opt_cfg)

        # single device reference
        p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

        p_sh = S.tree_shardings(jax.eval_shape(lambda: params), axes, rules, mesh)
        params_dev = jax.tree.map(jax.device_put, params, p_sh)
        def fn(p, o, b):
            with activation_rules(rules):
                return step(p, o, b)
        from jax import set_mesh
        with set_mesh(mesh):
            p2, o2, m = jax.jit(fn, in_shardings=(p_sh, None, None))(params_dev, opt, batch)
        diff = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                   for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)))
        print(json.dumps(dict(loss=float(m["loss"]), ref=float(m_ref["loss"]),
                              maxdiff=diff)))
    """), devices=4)
    assert abs(res["loss"] - res["ref"]) < 1e-2
    assert res["maxdiff"] < 5e-2


def test_compressed_psum_multidevice():
    res = _run_subprocess(textwrap.dedent("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.compression import compressed_psum
        mesh = jax.make_mesh((4,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64)), jnp.float32)
        def f(x):
            return compressed_psum(x[0], "d", jax.random.PRNGKey(0))
        y = jax.jit(shard_map(f, mesh=mesh, in_specs=P("d", None),
                              out_specs=P()))(x)
        exact = np.asarray(x).sum(0)
        err = float(np.abs(np.asarray(y) - exact).max())
        scale = float(np.abs(np.asarray(x)).max() / 127 * 4)
        print(json.dumps(dict(err=err, tol=scale * 1.5)))
    """), devices=4)
    assert res["err"] <= res["tol"]
