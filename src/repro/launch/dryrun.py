import os
# 512 virtual host devices: a CPU-only program by construction, so pin the
# platform too (an attached accelerator stays free for other processes)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production meshes and extract the roofline terms.

MUST be run as a script/module (the XLA_FLAGS/JAX_PLATFORMS lines above
precede every jax import):  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b \
             --shape train_4k [--multi-pod] [--out results/dryrun]

Per cell this emits a JSON record with:
  * memory_analysis (per-device argument/output/temp/peak bytes),
  * cost_analysis FLOPs + bytes accessed (per-device SPMD program),
  * collective bytes by kind (post-SPMD HLO walk, while-loop trip counts
    folded in — launch/hlo_analysis.py),
  * the three roofline terms vs the TPU v5e-like hardware model and the
    MODEL_FLOPS/HLO_FLOPs usefulness ratio.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from jax import set_mesh
from repro.models import counting
from repro.models.config import SHAPES
from repro import models
from repro.launch.mesh import make_production_mesh
from repro.launch import specs as S
from repro.launch.hlo_analysis import analyze_hlo
from repro.parallel.sharding import (DEFAULT_RULES, activation_rules,
                                     rules_for_mesh)
from repro.train import AdamWConfig, make_train_step
from repro.train.train_step import TrainStepConfig
from repro.train.optimizer import abstract_opt_state

# ---- hardware model (TPU v5e-like; per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

# long_500k runs only for sub-quadratic archs (DESIGN.md §Arch-applicability)
LONG_OK = {"mamba2-130m", "recurrentgemma-2b"}

# per-arch gradient-accumulation defaults sized so train_4k activations fit
# the 16 GB/chip budget (EXPERIMENTS.md §Perf, memory audit)
MICROBATCH_DEFAULTS = {
    "mistral-nemo-12b": 2, "qwen3-4b": 1, "starcoder2-3b": 2, "gemma2-2b": 2,
    "mamba2-130m": 1, "whisper-medium": 1, "recurrentgemma-2b": 2,
    "llama-3.2-vision-11b": 8, "grok-1-314b": 16, "deepseek-v2-lite-16b": 16,
}


def cells(arch=None, shape=None):
    for a in ARCH_IDS + ["rmat-coloring"]:
        if arch and a != arch:
            continue
        if a == "rmat-coloring":
            if shape in (None, "coloring"):
                yield a, "coloring"
            continue
        for s in SHAPES:
            if shape and s != shape:
                continue
            if s == "long_500k" and a not in LONG_OK:
                continue
            yield a, s


def _opt_cfg(cfg):
    # bf16 moments for the giants so optimizer state fits 16 GB/chip
    big = counting.param_count(cfg) > 50e9
    return AdamWConfig(moment_dtype="bfloat16" if big else "float32")


def lower_cell(arch: str, shape_name: str, mesh, rules=DEFAULT_RULES,
               bf16_params: bool = False, microbatches: int = 1):
    """Build + lower one cell; returns (lowered, meta)."""
    if arch == "rmat-coloring":
        return lower_coloring(mesh)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    params_abs, params_axes = models.init_params(cfg, None)
    p_sh = S.tree_shardings(params_abs, params_axes, rules, mesh)

    if shape.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        opt_abs = abstract_opt_state(params_abs, opt_cfg)
        o_sh = S.tree_shardings(
            opt_abs["m"], params_axes, rules, mesh)
        opt_sh = {"m": o_sh, "v": o_sh, "step": S.scalar_sharding(mesh)}
        batch_abs = S.batch_specs(cfg, shape)
        b_sh = S.tree_shardings(batch_abs, S.batch_axes(cfg), rules, mesh)
        step = make_train_step(cfg, opt_cfg,
                               TrainStepConfig(bf16_compute_params=bf16_params,
                                               microbatches=microbatches))

        def fn(params, opt_state, batch):
            with activation_rules(rules):
                return step(params, opt_state, batch)

        with set_mesh(mesh):
            lowered = jax.jit(
                fn,
                in_shardings=(p_sh, opt_sh, b_sh),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, batch_abs)
        return lowered, cfg, shape

    if shape.kind == "prefill":
        batch_abs = S.batch_specs(cfg, shape)
        b_sh = S.tree_shardings(batch_abs, S.batch_axes(cfg), rules, mesh)
        cache_abs, cache_axes = models.cache_spec(
            cfg, shape.global_batch, shape.seq_len)
        c_sh = S.tree_shardings(cache_abs, cache_axes, rules, mesh)

        def fn(params, batch, caches):
            with activation_rules(rules):
                logits, aux, caches = models.forward(cfg, params, batch,
                                                     caches=caches)
                # serving returns last-position logits only
                return logits[:, -1], caches

        with set_mesh(mesh):
            lowered = jax.jit(
                fn, in_shardings=(p_sh, b_sh, c_sh), donate_argnums=(2,),
            ).lower(params_abs, batch_abs, cache_abs)
        return lowered, cfg, shape

    # decode
    cache_abs, cache_axes, tok_abs = S.decode_specs(cfg, shape)
    c_sh = S.tree_shardings(cache_abs, cache_axes, rules, mesh)
    t_sh = S.tree_shardings(
        tok_abs, ("cache_batch",), rules, mesh)

    def fn(params, caches, tokens):
        with activation_rules(rules):
            return models.decode_step(cfg, params, caches, tokens)

    with set_mesh(mesh):
        lowered = jax.jit(
            fn, in_shardings=(p_sh, c_sh, t_sh), donate_argnums=(1,),
        ).lower(params_abs, cache_abs, tok_abs)
    return lowered, cfg, shape


def lower_coloring(mesh):
    """The paper's own workload on the production mesh (scale-24 RMAT)."""
    from repro.configs.rmat_coloring import get_config as get_col
    from repro.core.distance2 import MODELS
    from repro.core.distributed import build_distributed_coloring
    from repro.core.engine import get_backend
    ccfg = get_col()
    if get_backend(ccfg.engine).needs_ell:
        raise ValueError(
            f"dry-run lowers the coloring cell from shapes alone; the "
            f"{ccfg.engine!r} engine needs a real host graph for its ELL "
            "width — use engine='sort' or 'bitmap' here (ELL engines run "
            "via color_distributed)")
    if ccfg.model not in MODELS:
        raise ValueError(f"unknown coloring model {ccfg.model!r}")
    D = int(np.prod(mesh.devices.shape))
    v = 1 << ccfg.dryrun_scale
    e2 = 2 * ccfg.edge_factor * v
    if ccfg.model != "d1":
        # d2/pd2 color the squared constraint graph: |E(G2)| is bounded by
        # the wedge count ~ avg_degree x |directed edges| (distance2.py) —
        # the slab widens accordingly, everything else is shape-identical
        e2 *= 2 * ccfg.edge_factor
    vl = -(-v // D)
    el = int(e2 / D * 1.35)  # slab padding headroom for R-MAT skew
    fcv = fce = 0
    if ccfg.frontier != "off":
        # per-shard frontier slabs on the same static envelope: frontier
        # rounds + the compacted halo wire lower here too. Shapes-only
        # caveat: with no host graph there is no max-degree term, so on
        # skewed graphs the runtime edge slab can be wider than this
        # lowering's (the vertex slab and program structure are identical)
        from repro.core.frontier import frontier_capacities
        fcv, fce = frontier_capacities(vl, el,
                                       capacity=ccfg.frontier_capacity)
    # shapes-only halo slab: no host graph to classify boundary from, so
    # lower the worst case (every local vertex boundary, Bl = Vl); the
    # packed-entry width takes color_bound for the same reason (no provable
    # Delta+1 without a graph — matches the config's color_bound caveat)
    wire = "full" if ccfg.wire == "full" else "boundary"
    fn = build_distributed_coloring(mesh, vl, el, ccfg.local_concurrency,
                                    ccfg.max_rounds, engine=ccfg.engine,
                                    max_colors=ccfg.color_bound,
                                    frontier_cap_v=fcv, frontier_cap_e=fce,
                                    wire=wire, wire_colors=ccfg.color_bound)
    lsrc = jax.ShapeDtypeStruct((D, el), jnp.int32)
    ldst = jax.ShapeDtypeStruct((D, el), jnp.int32)
    bnd = jax.ShapeDtypeStruct((D, vl), jnp.int32)
    with set_mesh(mesh):
        lowered = fn.lower(lsrc, ldst, bnd)
    return lowered, ccfg, None


def analyse(lowered, cfg, shape, mesh, arch, shape_name, compile_s):
    compiled = lowered.compile()
    n_dev = int(np.prod(mesh.devices.shape))
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    st = analyze_hlo(hlo)

    # cost_analysis counts while bodies once (verified) -> use the HLO walk,
    # which folds trip counts; keep cost_analysis numbers for reference.
    flops_dev = st.dot_flops
    bytes_dev = st.boundary_bytes
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "devices": n_dev,
        "compile_seconds": compile_s,
        "per_device": {
            "flops": flops_dev,
            "bytes_accessed": bytes_dev,
            "collective_bytes": st.collective_bytes,
            "collective_by_kind": st.collective_bytes_by_kind,
            "collective_counts": st.collective_counts,
            "while_trip_counts": st.while_trip_counts,
            "cost_analysis_flops_once": float(cost.get("flops", 0.0)),
            "cost_analysis_bytes_once": float(cost.get("bytes accessed", 0.0)),
        },
        "memory_analysis": {},
    }
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        try:
            rec["memory_analysis"][attr] = int(getattr(mem, attr))
        except Exception:
            pass

    # roofline terms (per chip; chips divide out of the global form)
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = st.collective_bytes / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    rec["roofline"] = {
        **terms,
        "dominant": dominant,
        "bound_s": max(terms.values()),
    }
    if shape is not None:
        mf = counting.model_flops(cfg, shape)
        rec["model_flops_total"] = mf
        rec["model_flops_per_device"] = mf / n_dev
        rec["useful_flops_ratio"] = (mf / n_dev) / flops_dev if flops_dev else 0.0
        # roofline fraction: ideal model-FLOPs time / achieved bound
        ideal = mf / n_dev / PEAK_FLOPS
        rec["roofline_fraction"] = ideal / max(terms.values()) if max(terms.values()) else 0.0
    return rec


def run_cell(arch, shape_name, multi_pod, out_dir, rules=None,
             tag="baseline", bf16_params=False, microbatches=1):
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for_mesh(mesh, rules or DEFAULT_RULES)
    t0 = time.time()
    lowered, cfg, shape = lower_cell(arch, shape_name, mesh, rules,
                                     bf16_params=bf16_params,
                                     microbatches=microbatches)
    t_lower = time.time() - t0
    t0 = time.time()
    rec = analyse(lowered, cfg, shape, mesh, arch, shape_name,
                  compile_s=None)
    rec["compile_seconds"] = time.time() - t0
    rec["lower_seconds"] = t_lower
    rec["tag"] = tag
    os.makedirs(out_dir, exist_ok=True)
    mp = "multipod" if multi_pod else "pod"
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mp}__{tag}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] {arch} x {shape_name} x {mp}: "
          f"compile={rec['compile_seconds']:.1f}s "
          f"flops/dev={rec['per_device']['flops']:.3e} "
          f"coll/dev={rec['per_device']['collective_bytes']:.3e}B "
          f"dominant={rec['roofline']['dominant']} "
          f"frac={rec.get('roofline_fraction', 0):.3f}")
    # memory_analysis headline: prove it fits
    ma = rec["memory_analysis"]
    print(f"         memory/device: args={ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
          f"temp={ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
          f"out={ma.get('output_size_in_bytes', 0)/2**30:.2f}GiB")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--bf16-params", action="store_true",
                    help="mixed precision: bf16 compute params (H-A1)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="gradient-accumulation microbatches for train cells "
                         "(0 = per-arch MICROBATCH_DEFAULTS)")
    args = ap.parse_args()

    failures = []
    for arch, shape_name in cells(args.arch, args.shape):
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        mb = args.microbatches or MICROBATCH_DEFAULTS.get(arch, 1)
        for mp in meshes:
            try:
                run_cell(arch, shape_name, mp, args.out, tag=args.tag,
                         bf16_params=args.bf16_params,
                         microbatches=mb)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape_name, mp, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("dry-run complete: all cells lowered + compiled.")


if __name__ == "__main__":
    main()
