"""Serving driver: batched prefill + decode loop with the paged KV cache.

Demonstrates the AMU serving path end-to-end: requests arrive in batches,
prefill fills the cache, decode streams tokens; with --use-kernels prefill
runs the flash_attention and decode the paged_attention Pallas kernel
(interpret mode on CPU, compiled on TPU). Weights are held in bf16.

`serve()` is the whole path for one batch; `main()` and `chip_smoke.py` both
call it. It compiles prefill and decode ahead of time and times each with
its warm-up excluded: prefill runs twice and the second run is timed;
decode's first step is the warm-up and the rest are timed.

With --offload-kv the KV cache lives in host memory between decode steps
(:class:`~repro.runtime.offload.OffloadedKVCache`): each step fetches the
cache pages through the resident window (prefetch-ahead, AMI-style), runs
decode, and update()s the new pages back. The driver decodes once without
offload and once with, and asserts the generated tokens are identical —
the runtime twin of the simulator's `paged_kv_serve` differential check.
"""
from __future__ import annotations

import argparse
import functools
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import ModelConfig
from repro.models import lm
from repro.runtime import steps as steps_mod
from repro.runtime.compile_cache import enable_compile_cache


@dataclass
class ServeResult:
    tokens: jnp.ndarray            # [B, max_new] generated tokens
    logits: List[jnp.ndarray]      # [B, V] per step: prefill, then decode
    compiled: Dict[str, Any]       # "prefill" / "decode" -> jax Compiled
    compile_s: float               # prefill + decode compilation
    prefill_s: float               # one warm prefill of the whole batch
    decode_s: Optional[float]      # warm decode steps (all but the first)
    decode_steps: int              # how many steps decode_s covers


def init_params(cfg: ModelConfig, seed: int = 0):
    """Random serving weights, made in bf16 on the device under jit."""
    def init_bf16(key):
        return lm.init_model(cfg, key, dtype=jnp.bfloat16)
    return jax.jit(init_bf16)(jax.random.PRNGKey(seed))


def serve(cfg: ModelConfig, params, prompts: jnp.ndarray, max_new: int, *,
          use_kernels: bool = False, temperature: float = 0.0,
          feed: Optional[jnp.ndarray] = None, kv=None) -> ServeResult:
    """Prefill `prompts` [B, S] and decode `max_new` tokens.

    `feed` [B, max_new] replaces sampling with the given tokens (teacher
    forcing), so two runs can be compared logit for logit. With `kv` (an
    OffloadedKVCache) the cache pages through host memory between steps
    (fetch -> decode -> update).
    """
    key = jax.random.PRNGKey(0)
    B, S = prompts.shape
    batch = {"tokens": prompts}
    cache = lm.init_cache(cfg, B, S + max_new)
    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    par = configs.ParallelConfig()
    t0 = time.perf_counter()
    prefill = jax.jit(steps_mod.make_prefill_step(
        cfg, par, use_kernels)).lower(params, batch, cache).compile()
    decode = jax.jit(steps_mod.make_serve_step(
        cfg, par, use_kernels)).lower(params, tok_spec, cache).compile()
    compile_s = time.perf_counter() - t0

    jax.block_until_ready(prefill(params, batch, cache))    # warm-up
    t0 = time.perf_counter()
    logits, cache = jax.block_until_ready(prefill(params, batch, cache))
    prefill_s = time.perf_counter() - t0

    def next_token(lg, i, k):
        if feed is not None:
            return feed[:, i:i + 1]
        if temperature <= 0:
            return jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
        return jax.random.categorical(
            k, lg[:, -1] / temperature)[:, None].astype(jnp.int32)

    tok = next_token(logits, 0, key)
    out, hist, cur = [tok], [logits[:, -1]], cache
    if kv is not None:
        leaves, treedef = jax.tree.flatten(cur)
        for i, leaf in enumerate(leaves):
            kv.host_put(i, jax.device_get(leaf))
        kv.prefetch(0)
    t0 = None
    for i in range(1, max_new):
        if i == 2:              # step 1 was the warm-up
            jax.block_until_ready((tok, lg, cur))
            t0 = time.perf_counter()
        if kv is not None:
            pages = [kv.fetch(j) for j in range(kv.num_layers)]
            cur = jax.tree.unflatten(treedef, pages)
        lg, cur = decode(params, tok, cur)
        if kv is not None:
            for j, leaf in enumerate(jax.tree.leaves(cur)):
                kv.update(j, leaf)
        key, sub = jax.random.split(key)
        tok = next_token(lg, i, sub)
        out.append(tok)
        hist.append(lg[:, -1])
    # with `feed`, tok does not depend on decode: wait on its outputs
    jax.block_until_ready((tok, cur, hist))
    decode_s = None if t0 is None else time.perf_counter() - t0
    return ServeResult(tokens=jnp.concatenate(out, axis=1), logits=hist,
                       compiled={"prefill": prefill, "decode": decode},
                       compile_s=compile_s, prefill_s=prefill_s,
                       decode_s=decode_s, decode_steps=max(max_new - 2, 0))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--offload-kv", action="store_true",
                    help="page the KV cache through OffloadedKVCache "
                         "between decode steps and check token identity")
    ap.add_argument("--offload-window", type=int, default=2,
                    help="resident window (device pages) for --offload-kv")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0,
                    help="wall-clock budget for the --offload-kv prefetch "
                         "drain; a hung worker fails the run with a "
                         "diagnostic instead of hanging CI")
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    assert cfg.is_decoder, f"{args.arch} is encoder-only; nothing to decode"
    params = init_params(cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    run = functools.partial(serve, cfg, params, prompts, args.max_new,
                            use_kernels=args.use_kernels,
                            temperature=args.temperature)

    res = run()
    tok_s = (f"{args.batch * res.decode_steps / res.decode_s:,.1f} tok/s"
             if res.decode_s else "n/a (too few steps to time)")
    print(f"compile: {res.compile_s:.2f}s | prefill: {args.batch}x"
          f"{args.prompt_len} in {res.prefill_s:.4f}s | decode: {tok_s} | "
          f"sample row 0: {np.asarray(res.tokens[0])[:12].tolist()}")

    if args.offload_kv:
        from repro.runtime.offload import OffloadedKVCache

        n_pages = len(jax.tree.leaves(jax.eval_shape(lambda: lm.init_cache(
            cfg, args.batch, args.prompt_len + args.max_new))))
        kv = OffloadedKVCache(num_layers=n_pages,
                              window=args.offload_window)
        t0 = time.time()
        gen_off = run(kv=kv).tokens
        t_off = time.time() - t0
        # drain under a wall-clock watchdog: close() blocks on in-flight
        # uploads and the writeback queue, so one wedged worker would
        # otherwise hang the CI step with no diagnostic
        drain = threading.Thread(target=kv.close, daemon=True)
        drain.start()
        drain.join(timeout=args.drain_timeout_s)
        if drain.is_alive():
            raise SystemExit(
                f"offload-kv drain hung: close() still blocked after "
                f"{args.drain_timeout_s:.1f}s (pending uploads: "
                f"{sorted(kv._pending)}, writebacks queued: "
                f"{kv._writeback_q.unfinished_tasks})")
        same = bool(jnp.array_equal(res.tokens, gen_off))
        print(f"offload-kv: {n_pages} pages, window {args.offload_window}, "
              f"{t_off:.2f}s | stats {kv.stats} | tokens identical: {same}")
        if not same:
            raise SystemExit("offloaded decode diverged from baseline")


if __name__ == "__main__":
    main()
