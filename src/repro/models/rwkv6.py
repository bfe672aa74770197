"""RWKV-6 "Finch" token mixing (arXiv:2404.05892) — attention-free recurrence
with data-dependent decay.

Per head (head size N = cfg.resolved_head_dim), with per-token receptance r,
key k, value v and decay w_t (data-dependent, in (0,1)) and bonus u:

    S_t = diag(w_t) S_{t-1} + k_t^T v_t            (state: [N, N])
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Train/prefill runs a lax.scan over time (linear in S, O(S * N^2) work);
decode is the same update for one token.
Token-shift mixing (lerp of x_{t-1}, x_t) uses a 1-token cache in decode.

Simplifications vs the reference implementation (documented): the low-rank
LoRA projections for decay/mix are collapsed into full-rank dense maps (same
FLOP order, fewer moving parts), and gating uses silu.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import _dense_init
from repro.runtime import hints

Params = Dict[str, Any]


def init_rwkv6(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    return {
        "w_r": _dense_init(ks[0], d, d, dtype),
        "w_k": _dense_init(ks[1], d, d, dtype),
        "w_v": _dense_init(ks[2], d, d, dtype),
        "w_g": _dense_init(ks[3], d, d, dtype),
        "w_w": (jax.random.normal(ks[4], (d, d), jnp.float32)
                * 0.01 / math.sqrt(d)).astype(dtype),   # decay projection
        "w_o": _dense_init(ks[5], d, d, dtype),
        "mix": jax.random.uniform(ks[6], (5, d), jnp.float32).astype(dtype),
        "decay_base": (jax.random.uniform(ks[7], (d,), jnp.float32, -8.0,
                                          -4.0)).astype(jnp.float32),
        "bonus": jnp.zeros((d,), jnp.float32),
    }


def _token_shift(x: jnp.ndarray,
                 last: Optional[jnp.ndarray]) -> jnp.ndarray:
    """x_{t-1} stream; `last` is the final token of the previous chunk."""
    if last is None:
        last = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([last, x[:, :-1]], axis=1)


def _project(cfg: ModelConfig, p: Params, x: jnp.ndarray,
             x_prev: jnp.ndarray):
    """Compute r, k, v, gate, decay for a chunk. x: [B, S, d]."""
    mix = p["mix"]
    def lerp(i):
        return x + (x_prev - x) * mix[i]
    r = lerp(0) @ p["w_r"]
    k = lerp(1) @ p["w_k"]
    v = lerp(2) @ p["w_v"]
    g = jax.nn.silu(lerp(3) @ p["w_g"])
    # data-dependent decay (Finch): w_t = exp(-exp(base + f(x)))
    wlog = p["decay_base"] + jnp.tanh(lerp(4) @ p["w_w"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog))                       # in (0, 1)
    return r, k, v, g, w


def _heads(cfg: ModelConfig, t: jnp.ndarray) -> jnp.ndarray:
    B, S, d = t.shape
    return t.reshape(B, S, cfg.num_heads, cfg.resolved_head_dim)


def rwkv6_chunk(cfg: ModelConfig, p: Params, r, k, v, w,
                state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential scan over the tokens. r/k/v/w: [B, S, H, N] (w f32).
    state: [B, H, N, N] (f32). Returns (out [B,S,H,N], new state)."""
    u = p["bonus"].reshape(cfg.num_heads, cfg.resolved_head_dim)

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                      # [B, H, N]
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)    # [B, H, N, N]
        o = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., None] * s + kv
        return s, o

    rs, ks_, vs, ws = (jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    new_state, out = jax.lax.scan(step, state, (rs, ks_, vs, ws))
    return jnp.moveaxis(out, 0, 1), new_state


def init_rwkv6_state(cfg: ModelConfig, batch: int) -> Dict[str, jnp.ndarray]:
    H, N = cfg.num_heads, cfg.resolved_head_dim
    return {"s": jnp.zeros((batch, H, N, N), jnp.float32),
            "last_x": jnp.zeros((batch, 1, cfg.d_model), jnp.float32)}


def apply_rwkv6(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                state: Optional[Dict[str, jnp.ndarray]] = None
                ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """RWKV-6 block. x: [B, S, d] -> [B, S, d]."""
    B, S, d = x.shape
    s0 = (state["s"] if state is not None
          else jnp.zeros((B, cfg.num_heads, cfg.resolved_head_dim,
                          cfg.resolved_head_dim), jnp.float32))
    last = state["last_x"].astype(x.dtype) if state is not None else None
    x_prev = _token_shift(x, last)
    r, k, v, g, w = _project(cfg, p, x, x_prev)
    rh, kh, vh = (_heads(cfg, t) for t in (r, k, v))
    wh = _heads(cfg, w.astype(jnp.float32))
    # mixer runs head-sharded over the "model" axis (64 heads / 16-way TP)
    dp = hints.batch_spec_axes()
    rh, kh, vh = (hints.constrain(t, dp, None, "model", None)
                  for t in (rh, kh, vh))
    wh = hints.constrain(wh, dp, None, "model", None)
    rh32, kh32, vh32 = (t.astype(jnp.float32) for t in (rh, kh, vh))
    out, s_new = rwkv6_chunk(cfg, p, rh32, kh32, vh32, wh, s0)
    out = out.astype(x.dtype).reshape(B, S, d)
    out = (out * g) @ p["w_o"]
    new_state = None
    if state is not None:
        new_state = {"s": s_new, "last_x": x[:, -1:].astype(jnp.float32)}
    return out, new_state
