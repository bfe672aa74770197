"""Serving example, two layers of the same mechanism:

Default: the paged-KV serving workload through the AMU session API —
open-loop request arrivals gather their KV pages from tiered far memory
(local / CXL / cross-switch) with one AMI vector gather per request, and
per-request completion-latency percentiles come back on `RunStats`. The
synchronous page-fault baseline runs first for the tail-latency contrast.

`--lm` instead runs a real transformer decode: batched requests through
prefill + paged decode, with the decode attention optionally running the
paged_attention Pallas kernel (`--use-kernels`) — KV pages streamed
through VMEM are the kernel twin of the far-memory gathers above.

Usage: PYTHONPATH=src python examples/serve_paged.py [--requests N]
       PYTHONPATH=src python examples/serve_paged.py --lm [--use-kernels]
"""
import argparse
import time


def serve_sim(requests: int) -> None:
    from repro.amu import AmuConfig, AmuSession
    from repro.core.serving import serve_regions

    base = AmuConfig(far=serve_regions(requests=requests))
    print(f"=== paged-KV serving, {requests} open-loop requests ===")
    print(f"{'data plane':>12s} {'p50':>8s} {'p99':>8s} {'p999':>8s} "
          f"{'MLP':>6s}")
    sync_mean = None
    for label, kw in (("page-fault", dict(data_plane="sync")),
                      ("ami", {}),
                      ("ami-vector", {})):
        cfg = base.derive(vector=(label == "ami-vector"))
        with AmuSession(cfg) as s:
            out = s.run("paged_kv_serve", requests=requests,
                        coroutines=16, **kw)
        assert out.verified
        sync_mean = sync_mean or out.req_mean_us
        print(f"{label:>12s} {out.req_p50_us:7.1f}u {out.req_p99_us:7.1f}u "
              f"{out.req_p999_us:7.1f}u {out.mlp:6.2f}"
              + (f"  ({sync_mean / out.req_mean_us:.1f}x mean vs page-fault)"
                 if label != "page-fault" else ""))
    print("\nMLP across concurrent requests is the whole mechanism: the "
          "AMI planes\noverlap every tenant's page gathers where the "
          "page-fault plane blocks.")


def serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import lm
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = configs.get_smoke_config(args.arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.max_new
    rng = np.random.default_rng(0)

    prefill = jax.jit(lambda p, b, c: lm.prefill(
        cfg, p, b, c, use_kernels=args.use_kernels))
    decode = jax.jit(lambda p, t, c: lm.decode_step(
        cfg, p, t, c, use_kernels=args.use_kernels))

    def serve_wave(wave: int) -> float:
        prompts = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len)))
        cache = lm.init_cache(cfg, args.batch, max_len)
        logits, cache = prefill(params, {"tokens": prompts}, cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        t0 = time.time()
        for _ in range(args.max_new - 1):
            logits, cache = decode(params, tok, cache)
            tok = jnp.argmax(logits[:, -1], -1)[:, None]
        jax.block_until_ready(tok)
        dt = time.time() - t0
        rate = args.batch * (args.max_new - 1) / dt
        print(f"wave {wave}: {rate:8.1f} tok/s "
              f"(paged kernel: {args.use_kernels})")
        return rate

    rates = [serve_wave(w) for w in range(2)]
    print(f"mean decode throughput: {sum(rates) / len(rates):.1f} tok/s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--lm", action="store_true",
                    help="run the transformer prefill+decode demo instead")
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--use-kernels", action="store_true")
    args = ap.parse_args()
    if args.lm:
        serve_lm(args)
    else:
        serve_sim(args.requests)


if __name__ == "__main__":
    main()
