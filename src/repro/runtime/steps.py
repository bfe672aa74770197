"""Step builders: jit-able train_step / prefill / serve_step closures with
donation and sharding attached — shared by the real train loop, the serving
loop, and the multi-pod dry-run (which lowers exactly these functions).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.models import lm
from repro.optim import adamw
from repro.runtime import sharding as shd

Params = Any


def make_train_step(cfg: ModelConfig, par: ParallelConfig,
                    opt_cfg: adamw.AdamWConfig,
                    use_kernels: bool = False,
                    moe_mode: str = "capacity") -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    par.microbatches > 1 -> gradient accumulation: the global batch is split
    along the batch dim and scanned, with full remat inside each microstep;
    activation peak shrinks ~1/n at the cost of re-walking the weights.
    """
    n_micro = max(par.microbatches, 1)

    def loss_fn(p, mb):
        loss, metrics = lm.train_loss(
            cfg, p, mb, use_kernels=use_kernels, moe_mode=moe_mode,
            remat=par.remat)
        return loss, metrics

    def train_step(params: Params, opt_state: Dict[str, Any],
                   batch: Dict[str, jnp.ndarray]):
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            micro = jax.tree.map(
                lambda t: t.reshape((n_micro, t.shape[0] // n_micro)
                                    + t.shape[1:]), batch)
            gzero = jax.tree.map(
                lambda t: jnp.zeros(t.shape, jnp.float32), params)

            def mb_step(carry, mb):
                gacc, lacc = carry
                (loss, _), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                gacc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), gacc, g)
                return (gacc, lacc + loss), None

            (gsum, lsum), _ = jax.lax.scan(
                mb_step, (gzero, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = lsum / n_micro
            metrics = {}
        params, opt_state, opt_metrics = adamw.apply_updates(
            opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, **metrics, **opt_metrics}
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, par: ParallelConfig,
                      use_kernels: bool = False,
                      moe_mode: str = "capacity") -> Callable:
    def prefill_step(params: Params, batch: Dict[str, jnp.ndarray],
                     cache: Params):
        return lm.prefill(cfg, params, batch, cache,
                          use_kernels=use_kernels, moe_mode=moe_mode)
    return prefill_step


def make_serve_step(cfg: ModelConfig, par: ParallelConfig,
                    use_kernels: bool = False,
                    moe_mode: str = "capacity") -> Callable:
    """One decode step: (params, tokens [B,1], cache) -> (logits, cache)."""
    def serve_step(params: Params, tokens: jnp.ndarray, cache: Params):
        return lm.decode_step(cfg, params, tokens, cache,
                              use_kernels=use_kernels, moe_mode=moe_mode)
    return serve_step


# ------------------------------------------------------------ jit packaging
def jit_train_step(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                   opt_cfg: adamw.AdamWConfig, params: Params,
                   shape: ShapeConfig, use_kernels: bool = False,
                   moe_mode: str = "capacity"):
    """jit with explicit param/opt-state shardings + donation of both.
    `params` may be abstract (jax.eval_shape). Returns (step, param,
    opt-state and batch shardings); batches arrive already placed."""
    p_sh = shd.params_shardings(cfg, par, mesh, params)
    o_sh = shd.opt_state_shardings(cfg, par, mesh, params)
    b_sh = shd.batch_shardings(cfg, par, mesh, shape)
    step = make_train_step(cfg, par, opt_cfg, use_kernels, moe_mode)
    return jax.jit(
        step,
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if par.donate_state else (),
    ), p_sh, o_sh, b_sh
