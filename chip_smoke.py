"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a 2x2 host; the sharded path only

On one chip it runs each Pallas kernel once at real width against
`repro.kernels.ref`, then serves the unmodified qwen2.5-3b config (36 layers,
published widths, random bf16 weights from a seed) through
`repro.launch.serve.serve()`: 8 requests of 960 prompt tokens and 64 new
tokens, once with the kernels (flash prefill, paged decode) and once with
the jnp path, and compares the logits of prefill and of every decode step.

With --four-chips it runs only the sharded path: granite-moe-1b-a400m at
full width and depth takes 3 train steps through `repro.launch.train.train()`
on a (data=2, model=2) mesh, and the same config cut to 2 layers takes one
step on that mesh and one on a one-device mesh, which must agree.

It exits non-zero, printing no result, when JAX finds no TPU. Timings it
prints are smoke numbers (one run, warm-up excluded), not benchmark results.
The last line of its output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Tolerances, each with its reason. Exact comparisons need none: gather
# copies rows, and xor on int32 has no rounding.
# Triad: the kernel computes b + s*c in f32 as a multiply and an add; XLA
# may fuse the reference into one FMA, one rounding apart.
TRIAD_TOL = 1e-6
# Attention kernels vs the f32 reference (HIGHEST precision) on the same
# bf16 inputs, as max |err| over max |ref|: the kernels' in-kernel matmuls
# run at the MXU's default precision and both outputs are rounded to bf16,
# a few bf16 roundings (up to 5.65e-3 on a v5e). A wrong head, page or mask
# gives errors of order one.
ATTN_TOL = 2e-2
# Serving logits, kernel path vs jnp path, teacher-forced on the same
# tokens: the jnp path rounds attention logits and probabilities to bf16
# where the kernels compute them in f32, so each of 36 layers differs by
# about one bf16 rounding of its attention output, and the residual stream
# carries that to the logits. The measure is the L2 norm of the difference
# over that of the jnp logits, per step (2.0e-2 on a v5e).
SERVE_REL_L2 = 5e-2
# 2-layer train step, 4-chip mesh vs one chip, same data and init: the mesh
# splits contractions over "model" and rounds each bf16 partial sum before
# the all-reduce. The loss averages that over every token; the gradient
# norm sums squares and keeps more of it (1.6e-5 and 2.7e-5 on a v5e).
LOSS_RTOL, GNORM_RTOL = 1e-3, 1e-2


def require_tpu(count: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU; JAX found {devices[0].platform!r}")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devices)}")
    return devices


def check(name: str, ok: bool, detail: str) -> None:
    print(f"{name}: {'ok' if ok else 'FAILED'} ({detail})", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: {name} failed: {detail}")


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


# ------------------------------------------------------------------ kernels
def kernel_phase(seed: int, vocab: int, d: int, rows: int, seq: int) -> None:
    """Each kernel once against ref.py: gather and scatter over a
    [vocab, d] table, attention over `seq` keys."""
    from repro.kernels import ops, ref

    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    tiles = (vocab,) + ops.row_shape(d)

    table = jax.jit(lambda key: jax.random.normal(key, tiles, jnp.bfloat16))(
        k[0])
    idx = jax.random.randint(k[1], (rows,), 0, vocab)
    out = ops.gather(table, idx)
    check(f"gather {vocab}x{d} bf16", bool(jnp.array_equal(
        out, ref.gather_ref(table, idx))), f"exact, {rows} rows")
    del table, out

    itable = jax.random.randint(k[2], tiles, 0, 1 << 30, jnp.int32)
    # half the updates on 64 hot rows: conflicts exercise the drain path
    half = rows // 32
    sidx = jnp.concatenate([jax.random.randint(k[3], (half,), 0, vocab),
                            jax.random.randint(k[4], (half,), 0, 64)])
    upd = jax.random.randint(k[5], (2 * half,) + tiles[1:], 0, 1 << 30,
                             jnp.int32)
    out = ops.scatter_update(itable, sidx, upd, op="xor")
    check(f"scatter xor {vocab}x{d} int32", bool(jnp.array_equal(
        out, ref.scatter_update_ref(itable, sidx, upd, "xor"))),
        f"exact, {2 * half} updates, {half} on 64 hot rows")
    del itable, upd, out

    b = jax.random.normal(k[6], (rows * 1024,), jnp.float32)
    c = jax.random.normal(k[7], (rows * 1024,), jnp.float32)
    r = ref.triad_ref(b, c, 3.0)
    err = float(jnp.max(jnp.abs(ops.triad(b, c, 3.0) - r) / (1.0 + jnp.abs(r))))
    check(f"triad {rows * 1024} f32", err <= TRIAD_TOL,
          f"max |err|/(1+|ref|) {err!r}")
    del b, c

    def close(a, r):
        a, r = a.astype(jnp.float32), r.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))

    # qwen2.5-3b, granite-moe-1b-a400m, kimi-k2-1t-a32b
    for hq, hkv, hd in ((16, 2, 128), (16, 8, 64), (64, 8, 128)):
        ks = jax.random.split(jax.random.PRNGKey(seed + hq + hd), 4)
        q = jax.random.normal(ks[0], (8, hq, hd), jnp.bfloat16)
        kc = jax.random.normal(ks[1], (8, seq, hkv * hd), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (8, seq, hkv * hd), jnp.bfloat16)
        lens = jax.random.randint(ks[3], (8,), 1, seq + 1)
        with jax.default_matmul_precision("highest"):
            r = ref.paged_attention_ref(q, kc, vc, lens)
        err = close(ops.paged_attention(q, kc, vc, lens), r)
        check(f"paged attention {hq}/{hkv} heads, d{hd}", err <= ATTN_TOL,
              f"max |err|/max |ref| {err!r}, T={seq}")

    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(ks[0], (2, seq, 16, 128), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (2, seq, 2, 128), jnp.bfloat16)
    vv = jax.random.normal(ks[2], (2, seq, 2, 128), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        r = ref.attention_ref(*(jnp.swapaxes(x, 1, 2) for x in (q, kk, vv)))
    err = close(ops.flash_attention(q, kk, vv), jnp.swapaxes(r, 1, 2))
    check("flash attention 16/2 heads, d128", err <= ATTN_TOL,
          f"max |err|/max |ref| {err!r}, S={seq} causal")


# -------------------------------------------------------------------- serve
def serve_phase(cfg, batch: int, prompt_len: int, max_new: int,
                seed: int) -> None:
    from repro.launch import serve

    params = serve.init_params(cfg, seed)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    runs = {}
    for use_kernels in (True, False):
        feed = runs[True].tokens if runs else None
        res = serve.serve(cfg, params, prompts, max_new,
                          use_kernels=use_kernels, feed=feed)
        runs[use_kernels] = res
        print(f"smoke timing, not a benchmark ({cfg.name}, kernels="
              f"{use_kernels}): compile {res.compile_s!r} s | prefill "
              f"{batch}x{prompt_len} {res.prefill_s!r} s | decode "
              f"{res.decode_steps} steps x {batch} {res.decode_s!r} s",
              flush=True)
    for step in ("prefill", "decode"):
        check(f"{step} compiled with Pallas kernels",
              has_kernel(runs[True].compiled[step]), "tpu_custom_call")
    worst, finite = 0.0, True
    for a, r in zip(runs[True].logits, runs[False].logits):
        a, r = a.astype(jnp.float32), r.astype(jnp.float32)
        finite &= bool(jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(r)))
        worst = max(worst, float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)))
    check("serve logits finite", finite, "both runs, every step")
    check(f"serve logits, kernels vs jnp ({cfg.num_layers} layers)",
          worst <= SERVE_REL_L2,
          f"worst per-step rel L2 {worst!r} over {len(runs[True].logits)} "
          f"steps, shape {tuple(runs[True].logits[0].shape)}")


# -------------------------------------------------------------------- train
def train_phase(cfg, devices, batch: int, seq: int, steps: int,
                seed: int) -> None:
    from repro import configs
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    par = configs.ParallelConfig(remat="full")
    shape = configs.ShapeConfig("smoke_train", seq, batch, configs.KIND_TRAIN)
    mesh = make_mesh(devices[:4], (2, 2))
    t0 = time.perf_counter()
    state = train.train(cfg, shape, mesh, steps, par=par, seed=seed)
    losses = [float(m["loss"]) for m in state["history"]]
    print(f"smoke timing, not a benchmark ({cfg.name} train, "
          f"{batch}x{seq}): {steps} steps incl. compile "
          f"{time.perf_counter() - t0!r} s", flush=True)
    check(f"{cfg.name} {cfg.num_layers} layers, {steps} steps on 2x2",
          len(losses) == steps and all(np.isfinite(losses)),
          f"losses {losses}")
    peaks = [peak_bytes(d) for d in devices[:4]]
    print("peak_bytes_in_use per device: "
          + ", ".join(f"{d.id}: {p}" for d, p in zip(devices, peaks)))
    check("state spread over the mesh", max(peaks) < 0.5 * sum(peaks),
          f"largest device peak is {max(peaks) / sum(peaks):.3f} of the sum")
    del state

    # a quarter of the sequence: at 8x4096 even the 2-layer step needs
    # 17.02 GB on one chip (compiled for a described v5e)
    cut = dataclasses.replace(cfg, num_layers=2)
    cmp_shape = configs.ShapeConfig("smoke_cmp", seq // 4, batch,
                                    configs.KIND_TRAIN)
    got = {}
    for name, m in (("2x2", mesh), ("1 chip", make_mesh(devices[:1], (1, 1)))):
        st = train.train(cut, cmp_shape, m, 1, par=par, seed=seed)
        got[name] = (float(st["history"][0]["loss"]),
                     float(st["history"][0]["grad_norm"]))
        del st
    (l4, g4), (l1, g1) = got["2x2"], got["1 chip"]
    check("2-layer step, 2x2 vs 1 chip",
          abs(l4 - l1) <= LOSS_RTOL * abs(l1)
          and abs(g4 - g1) <= GNORM_RTOL * abs(g1),
          f"loss {l4!r} vs {l1!r}, grad norm {g4!r} vs {g1!r}, "
          f"{batch}x{seq // 4}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train path on a 2x2 mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = require_tpu(4 if args.four_chips else 1)
    from repro import configs
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.update([name]))

    if args.four_chips:
        train_phase(configs.get_config("granite-moe-1b-a400m"), devices,
                    8, 4096, 3, args.seed)
    else:
        kernel_phase(args.seed, vocab=151936, d=2048, rows=65536, seq=2048)
        serve_phase(configs.get_config("qwen2.5-3b"), 8, 960, 64, args.seed)

    hits = events["/jax/compilation_cache/cache_hits"]
    misses = events["/jax/compilation_cache/cache_misses"]
    print(f"compile cache {cache_dir}: {hits} hits, {misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
