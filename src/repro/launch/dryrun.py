import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init). Everything below is ordinary.
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from functools import partial  # noqa: E402
from typing import Any, Dict  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.configs.base import KIND_PREFILL, KIND_TRAIN  # noqa: E402
from repro.data.pipeline import input_specs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime import hints  # noqa: E402
from repro.runtime import sharding as shd  # noqa: E402
from repro.runtime import steps as steps_mod  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# --------------------------------------------------------------- HW constants
PEAK_FLOPS = 197e12        # bf16 / chip (v5e-class)
HBM_BW = 819e9             # B/s per chip
ICI_BW = 50e9              # B/s per link

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = {
    "all-gather": 1.0,
    "all-reduce": 2.0,            # reduce-scatter + all-gather ring cost
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


# --------------------------------------------------------------- cell builder
def build_cell(arch: str, shape_name: str, mesh, par=None,
               moe_mode: str = "capacity", microbatches: int = 0,
               params_bf16: bool = False):
    """Returns (lower_fn, arg_specs) for one (arch x shape x mesh) cell."""
    cfg = configs.get_config(arch)
    shape = configs.get_shape(shape_name)
    ok, reason = configs.shape_applicable(cfg, shape)
    if not ok:
        return None, reason
    par = par or configs.default_parallel(cfg, shape)
    if microbatches:
        import dataclasses
        par = dataclasses.replace(par, microbatches=microbatches)

    key = jax.random.PRNGKey(0)
    # >=100B-param configs hold weights in bf16 (f32 masters would exceed
    # the fleet's HBM; the optimizer keeps f32 math on bf16 moments)
    p_dtype = (jnp.bfloat16 if (cfg.param_count() > 100e9 or params_bf16)
               else jnp.float32)
    params_sds = jax.eval_shape(lambda: lm.init_model(cfg, key,
                                                      dtype=p_dtype))
    p_sh = shd.params_shardings(cfg, par, mesh, params_sds)
    params_sds = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_sds, p_sh)
    b_sh = shd.batch_shardings(cfg, par, mesh, shape)
    batch_sds = input_specs(cfg, shape, sharding_fn=lambda n: None)
    batch_sds = {k: jax.ShapeDtypeStruct(
        v.shape, v.dtype,
        sharding=b_sh.get(k if k in b_sh else "tokens"))
        for k, v in batch_sds.items()}

    if shape.kind == KIND_TRAIN:
        moment_dtype = (jnp.bfloat16 if cfg.param_count() > 100e9
                        else jnp.float32)
        opt_sds = jax.eval_shape(
            partial(adamw.init_state, moment_dtype=moment_dtype), params_sds)
        o_sh = shd.opt_state_shardings(cfg, par, mesh, params_sds)
        opt_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            opt_sds, o_sh)
        opt_cfg = adamw.AdamWConfig()
        step = steps_mod.make_train_step(cfg, par, opt_cfg,
                                         use_kernels=False,
                                         moe_mode=moe_mode)
        fn = jax.jit(step, out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
        args = (params_sds, opt_sds, batch_sds)
    elif shape.kind == KIND_PREFILL:
        cache_sds = None
        if cfg.is_decoder:
            cache_sds = jax.eval_shape(
                lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len))
            c_sh = shd.cache_shardings(cfg, par, mesh, cache_sds)
            cache_sds = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                cache_sds, c_sh)
            step = steps_mod.make_prefill_step(cfg, par, moe_mode=moe_mode)
            fn = jax.jit(step, donate_argnums=(2,))
            args = (params_sds, batch_sds, cache_sds)
        else:
            # encoder-only: full forward, no cache
            def enc_fwd(params, batch):
                return lm.prefill(cfg, params, batch, None,
                                  moe_mode=moe_mode)[0]
            fn = jax.jit(enc_fwd)
            args = (params_sds, batch_sds)
    else:  # decode
        cache_sds = jax.eval_shape(
            lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len))
        c_sh = shd.cache_shardings(cfg, par, mesh, cache_sds)
        # pretend the cache is full (len = seq_len) — shapes are what matter
        cache_sds = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            cache_sds, c_sh)
        step = steps_mod.make_serve_step(cfg, par, moe_mode=moe_mode)
        fn = jax.jit(step, donate_argnums=(2,))
        args = (params_sds, batch_sds["tokens"], cache_sds)
    return (fn, args), ""


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             moe_mode: str = "capacity",
             microbatches: int = 0,
             params_bf16: bool = False) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    hints.set_mesh(mesh)
    t0 = time.time()
    built, reason = build_cell(arch, shape_name, mesh, moe_mode=moe_mode,
                               microbatches=microbatches,
                               params_bf16=params_bf16)
    if built is None:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "skipped": True, "reason": reason}
    fn, args = built
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
    hints.set_mesh_axes(None)
    counts = hlo_analysis.analyze(hlo)
    cfg = configs.get_config(arch)
    shape = configs.get_shape(shape_name)
    tokens = shape.tokens_per_step
    n_active = cfg.active_param_count()
    mult = 6 if shape.kind == KIND_TRAIN else 2
    model_flops = mult * n_active * tokens
    flops_dev = counts.flops
    bytes_dev = counts.hbm_bytes
    coll_dev = counts.ici_bytes
    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "chips": chips, "skipped": False,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "mem": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_gb": (mem.argument_size_in_bytes
                        + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes
                        - mem.alias_size_in_bytes) / 1e9,
        },
        "flops_per_dev": flops_dev,
        "bytes_per_dev": bytes_dev,
        "collective_bytes_per_dev": coll_dev,
        "collectives": dict(counts.by_collective),
        "collective_count": counts.collective_count,
        "while_trips": dict(counts.while_trips),
        "cost_analysis_flops": float(cost.get("flops", 0.0)),
        "cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
        "model_flops_total": model_flops,
        "terms": {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        },
        "useful_flops_ratio": (model_flops / chips) / max(flops_dev, 1.0),
    }
    terms = result["terms"]
    result["bottleneck"] = max(terms, key=terms.get)
    result["roofline_frac"] = max(
        result["useful_flops_ratio"] * terms["compute_s"] / max(sum(terms.values()), 1e-12), 0.0)
    return result


def apply_tuning(tune) -> None:
    """--tune rwkv.impl=chunked attn.q_chunk=1024 ... (perf iterations)."""
    from repro.models import blocks as _blocks
    from repro.models import rwkv6 as _rwkv6
    from repro.models import moe as _moe
    from repro.models import lm as _lm
    targets = {"attn": _blocks.ATTN_CONFIG, "rwkv": _rwkv6.RWKV_CONFIG,
               "moe": _moe.MOE_CONFIG, "lm": _lm.LM_CONFIG}
    for item in tune:
        key, _, val = item.partition("=")
        group, _, field = key.partition(".")
        cfgd = targets[group]
        old = cfgd[field]
        cfgd[field] = type(old)(int(val) if isinstance(old, int)
                                else float(val) if isinstance(old, float)
                                else val)
        print(f"# tune {group}.{field} = {cfgd[field]}", file=sys.stderr)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--moe-mode", default="capacity")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--params-bf16", action="store_true")
    ap.add_argument("--tune", action="append", default=[],
                    help="perf knobs, e.g. rwkv.impl=chunked "
                         "attn.chunk_threshold=4096 moe.sharded=1")
    ap.add_argument("--out", default="",
                    help="append JSONL results here")
    args = ap.parse_args()
    archs = configs.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(configs.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    apply_tuning(args.tune)
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                try:
                    res = run_cell(arch, shape_name, mp,
                                   moe_mode=args.moe_mode,
                                   microbatches=args.microbatches,
                                   params_bf16=args.params_bf16)
                except Exception as e:  # noqa: BLE001 — report and continue
                    res = {"arch": arch, "shape": shape_name,
                           "multi_pod": mp, "error": repr(e)[:500],
                           "skipped": False}
                    failures += 1
                line = json.dumps(res)
                print(line, flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(line + "\n")
    if failures:
        print(f"FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
