"""End-to-end training driver: config -> mesh -> sharded state -> supervised
loop with async checkpointing, straggler monitoring, and restart recovery.

On the CPU container this runs reduced configs on a debug mesh; on a TPU
host the same `train()` runs the full configs on the chips' mesh
(`chip_smoke.py --four-chips`; see dryrun.py for the compile-only proof at
256/512 chips). Parameters and optimizer state are made under jit straight
onto their shardings, and every batch is placed on the batch shardings.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax

from repro import configs
from repro.checkpoint.store import CheckpointStore
from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.data.pipeline import synthetic_batch
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import lm
from repro.optim import adamw
from repro.runtime import hints
from repro.runtime import steps as steps_mod
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.ft import StepMonitor, TrainSupervisor


def train(cfg: ModelConfig, shape: ShapeConfig, mesh, steps: int, *,
          par: ParallelConfig, seed: int = 0,
          store: Optional[CheckpointStore] = None,
          checkpoint_every: int = 10, resume: bool = False,
          monitor: Optional[StepMonitor] = None
          ) -> Dict[str, Any]:
    """Run `steps` train steps on `mesh`. Returns the final state with
    `history`, the metrics of each step run here. With `store=None` no
    checkpoint is written or read."""
    opt_cfg = adamw.AdamWConfig(total_steps=steps)
    key = jax.random.PRNGKey(seed)

    def init_params(k):
        return lm.init_model(cfg, k)

    hints.set_mesh(mesh)
    try:
        with mesh:
            abstract = jax.eval_shape(init_params, key)
            step_fn, p_sh, o_sh, b_sh = steps_mod.jit_train_step(
                cfg, par, mesh, opt_cfg, abstract, shape)
            params = jax.jit(init_params, out_shardings=p_sh)(key)
            opt_state = jax.jit(adamw.init_state, out_shardings=o_sh)(params)
            start = 0
            latest = store.latest_step() if store is not None else None
            if resume and latest is not None:
                # restore leaves directly onto their target shardings
                # (elastic: the writer's mesh/layout is irrelevant)
                sh_tree = {"params": p_sh, "opt_state": o_sh}
                flat, _ = jax.tree_util.tree_flatten_with_path(sh_tree)
                lookup = {jax.tree_util.keystr(path): sh
                          for path, sh in flat}
                restored, extra = store.restore(
                    latest, {"params": params, "opt_state": opt_state},
                    sharding_fn=lambda path, leaf: lookup[path])
                params, opt_state = restored["params"], restored["opt_state"]
                start = extra["step"]
                print(f"resumed from step {start}")

            def batch_fn(step):
                return {k: jax.device_put(v, b_sh[k]) for k, v in
                        synthetic_batch(cfg, shape, step, seed).items()}

            history: List[Dict[str, Any]] = []

            def logged_step(p, o, b):
                p, o, metrics = step_fn(p, o, b)
                history.append(metrics)
                return p, o, metrics

            sup = TrainSupervisor(store, checkpoint_every=checkpoint_every,
                                  monitor=monitor)
            state = sup.run({"params": params, "opt_state": opt_state,
                             "step": start}, logged_step, batch_fn, steps)
    finally:
        hints.set_mesh_axes(None)
    return {**state, "start": start, "history": history}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    shape = configs.ShapeConfig("cli_train", args.seq, args.batch,
                                configs.KIND_TRAIN)
    par = configs.ParallelConfig(remat="full",
                                 microbatches=args.microbatches)
    if args.production_mesh:
        mesh = make_production_mesh()
    elif jax.device_count() > 1:
        mesh = make_debug_mesh(min(8, jax.device_count()))
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
    monitor = StepMonitor(on_straggler=lambda s, d, e: print(
        f"[straggler] step {s}: {d:.3f}s vs ewma {e:.3f}s"))

    t0 = time.time()
    state = train(cfg, shape, mesh, args.steps, par=par,
                  store=CheckpointStore(args.checkpoint_dir),
                  checkpoint_every=args.checkpoint_every,
                  resume=args.resume, monitor=monitor)
    dt = time.time() - t0
    loss = float(state["metrics"]["loss"])
    tok_s = (args.steps - state["start"]) * shape.tokens_per_step / max(
        dt, 1e-9)
    print(f"done: {args.steps} steps, final loss {loss:.4f}, "
          f"{tok_s:,.0f} tok/s, stragglers={len(monitor.stragglers)}")


if __name__ == "__main__":
    main()
