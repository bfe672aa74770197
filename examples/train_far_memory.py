"""End-to-end driver: train a (reduced) model for a few hundred steps with
the full substrate — sharded state, gradient accumulation, async
checkpointing, fault injection + automatic restart, straggler monitor.

This is deliverable (b)'s "train ~100M model for a few hundred steps"
scaled to the CPU container; pass --full-size on a real cluster.

Usage: PYTHONPATH=src python examples/train_far_memory.py [--steps 200]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint.store import CheckpointStore
from repro.data.pipeline import synthetic_batch
from repro.models import lm
from repro.optim import adamw
from repro.runtime import steps as steps_mod
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.ft import StepMonitor, TrainSupervisor


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--fail-at", type=int, default=25,
                    help="inject a node failure at this step (-1: off)")
    ap.add_argument("--ckpt", default="/tmp/repro_example_ckpt")
    args = ap.parse_args()

    cfg = configs.get_smoke_config(args.arch)
    shape = configs.ShapeConfig("train", args.seq, args.batch, "train")
    par = configs.ParallelConfig(remat="full", microbatches=2)
    opt_cfg = adamw.AdamWConfig(learning_rate=1e-3, warmup_steps=10,
                                total_steps=args.steps)

    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    opt_state = adamw.init_state(params)
    step_fn = jax.jit(steps_mod.make_train_step(cfg, par, opt_cfg))

    def batch_fn(step):
        return {k: jnp.asarray(v)
                for k, v in synthetic_batch(cfg, shape, step).items()}

    monitor = StepMonitor(on_straggler=lambda s, d, e: print(
        f"  [straggler] step {s}: {d * 1e3:.0f}ms vs ewma {e * 1e3:.0f}ms"))
    sup = TrainSupervisor(CheckpointStore(args.ckpt), checkpoint_every=10,
                          monitor=monitor)
    print(f"training {cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"{args.steps} steps, failure injected at step {args.fail_at}")
    t0 = time.time()
    state = sup.run({"params": params, "opt_state": opt_state, "step": 0},
                    step_fn, batch_fn, args.steps,
                    fail_at=None if args.fail_at < 0 else args.fail_at)
    dt = time.time() - t0
    print(f"done in {dt:.1f}s | final loss {float(state['metrics']['loss']):.4f} "
          f"| restarts survived: {sup.restarts} "
          f"| stragglers: {len(monitor.stragglers)}")


if __name__ == "__main__":
    main()
