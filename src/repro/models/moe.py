"""Mixture-of-Experts FFN (kimi-k2: 384e top-8 + 1 shared; granite: 32e top-8).

The model runs the **capacity dispatch** (`apply_moe_capacity`): tokens are
sorted by expert and run through per-expert matmuls at a capacity bound.
`apply_moe_dense` multiplies every token by every expert through a dispatch
one-hot; it drops nothing, and tests compare the capacity dispatch with it.

The router adds the standard auxiliary load-balancing loss.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import _dense_init

Params = Dict[str, Any]


def init_moe(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    m = cfg.moe
    assert m is not None
    d, ff, E = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": _dense_init(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, ff), jnp.float32)
                   * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, ff), jnp.float32)
                 * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32)
                   / math.sqrt(ff)).astype(dtype),
    }
    if m.num_shared_experts:
        ffs = ff * m.num_shared_experts
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": _dense_init(k1, d, ffs, dtype),
                       "w_up": _dense_init(k2, d, ffs, dtype),
                       "w_down": _dense_init(k3, ffs, d, dtype)}
    return p


def route(cfg: ModelConfig, p: Params,
          x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing. x: [T, d] -> (weights [T, k], experts [T, k], aux loss)."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"])            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, m.top_k)          # [T, k]
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # aux loss: E * sum_e (fraction of tokens to e) * (mean router prob to e)
    T = x.shape[0]
    one_hot = jax.nn.one_hot(experts, m.num_experts, dtype=jnp.float32)
    frac = jnp.sum(one_hot, axis=(0, 1)) / (T * m.top_k)
    mean_prob = jnp.mean(probs, axis=0)
    aux = m.num_experts * jnp.sum(frac * mean_prob)
    return weights.astype(x.dtype), experts, aux


def apply_moe_dense(cfg: ModelConfig, p: Params,
                    x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense-dispatch reference. x: [B, S, d] -> ([B, S, d], aux_loss).

    Dispatch/combine are einsums against a [T, k, E] one-hot, so no token
    is dropped.
    """
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    weights, experts, aux = route(cfg, p, xt)
    one_hot = jax.nn.one_hot(experts, m.num_experts, dtype=x.dtype)  # [T,k,E]
    combine = jnp.einsum("tk,tke->te", weights, one_hot)             # [T,E]
    # dispatch every token to its experts: [E, T, d] would be huge; instead
    # contract tokens against experts blockwise: out = sum_e combine[t,e] *
    # f_e(x_t). With capacity-less dense routing we compute f_e lazily via
    # einsum over the stacked weights.
    gate = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    up = jnp.einsum("td,edf->tef", xt, p["w_up"])
    act = jax.nn.silu(gate) * up                                     # [T,E,ff]
    act = act * combine[..., None]
    out = jnp.einsum("tef,efd->td", act, p["w_down"])
    if m.num_shared_experts:
        sp = p["shared"]
        out = out + (jax.nn.silu(xt @ sp["w_gate"])
                     * (xt @ sp["w_up"])) @ sp["w_down"]
    return out.reshape(B, S, d), aux


def apply_moe_capacity(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Capacity-bounded dispatch. x: [B, S, d] -> ([B, S, d], aux_loss).

    Tokens are sorted by expert into per-expert matmuls at capacity
    C = ceil(T * k / E * capacity_factor). Overflowing tokens are dropped
    (Switch-style), so FLOPs follow the *active* params. A capacity factor
    of E / k gives C = T, which drops nothing."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    weights, experts, aux = route(cfg, p, xt)
    E, k = m.num_experts, m.top_k
    C = max(1, int(math.ceil(T * k / E * m.capacity_factor)))
    flat_e = experts.reshape(-1)                                  # [T*k]
    flat_w = weights.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    # position of each (token, expert) pair within its expert's queue
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)          # [T*k, E]
    pos_in_e = jnp.cumsum(one_hot, axis=0) - 1
    mypos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = mypos < C
    slot = jnp.where(keep, flat_e * C + mypos, E * C)             # drop -> pad
    # scatter tokens into [E*C+1, d] buffer
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xt[flat_tok])
    grouped = buf[:E * C].reshape(E, C, d)
    gate = jnp.einsum("ecd,edf->ecf", grouped, p["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", grouped, p["w_up"])
    act = jax.nn.silu(gate) * up
    eout = jnp.einsum("ecf,efd->ecd", act, p["w_down"]).reshape(E * C, d)
    eout = jnp.concatenate([eout, jnp.zeros((1, d), x.dtype)], axis=0)
    tok_out = eout[slot] * (flat_w * keep)[:, None]               # [T*k, d]
    out = jnp.zeros((T, d), x.dtype).at[flat_tok].add(tok_out)
    if m.num_shared_experts:
        sp = p["shared"]
        out = out + (jax.nn.silu(xt @ sp["w_gate"])
                     * (xt @ sp["w_up"])) @ sp["w_down"]
    return out.reshape(B, S, d), aux
