"""Transformer building blocks: norms, RoPE/M-RoPE, GQA attention, GLU MLPs.

Pure-JAX (functional, pytree params). Attention dispatches to the Pallas
flash/paged kernels via `repro.kernels.ops` when enabled, else the jnp
reference path. Every init matches the assigned architectures' knobs
(QKV bias, GQA kv heads, sliding window, M-RoPE sections, tied embeddings).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import ModelConfig
from repro.runtime import hints

Params = Dict[str, Any]


# --------------------------------------------------------------------- init
def _dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


# -------------------------------------------------------------------- norms
def init_norm(cfg: ModelConfig, dim: int, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


def apply_norm(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mrope_sections: Tuple[int, ...] = ()) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [B, S] or [3, B, S] for M-RoPE.

    M-RoPE (Qwen2-VL): the D/2 rotary frequencies are split into
    (temporal, height, width) sections, each rotated by its own position id.
    For text tokens the three position streams coincide and M-RoPE reduces
    to standard RoPE.
    """
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                     # [D/2]
    if mrope_sections and positions.ndim == 3:
        sec = jnp.cumsum(jnp.array((0,) + tuple(mrope_sections)))
        # section id per frequency -> which of the 3 position streams to use
        stream = jnp.zeros((D // 2,), jnp.int32)
        for i in range(len(mrope_sections)):
            stream = jnp.where((jnp.arange(D // 2) >= sec[i])
                               & (jnp.arange(D // 2) < sec[i + 1]), i, stream)
        # per-frequency positions: [B, S, D/2]
        pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)  # [B,S,3]
        pos = jnp.take_along_axis(
            pos, jnp.broadcast_to(stream[None, None, :],
                                  pos.shape[:2] + (D // 2,)), axis=-1)
        angles = pos * freqs[None, None, :]
    else:
        if positions.ndim == 3:
            positions = positions[0]
        angles = positions.astype(jnp.float32)[..., None] * freqs  # [B,S,D/2]
    # [x1, x2] -> [x1·cos − x2·sin, x2·cos + x1·sin], written as one
    # multiply-add with the halves swapped by a rotation within each head:
    # splitting the head and concatenating the halves instead makes the
    # compiler lay the q/k projections out head-major, which costs a
    # transposed copy of wq and wk in every layer at every decode step.
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    cos = jnp.concatenate([cos, cos], -1)[..., None, :].astype(x.dtype)
    sin = jnp.concatenate([-sin, sin], -1)[..., None, :].astype(x.dtype)
    return x * cos + jnp.roll(x, D // 2, axis=-1) * sin   # [B,S,H,D]


# ---------------------------------------------------------------- attention
def init_attention(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(kq, d, cfg.num_heads * hd, dtype),
        "wk": _dense_init(kk, d, cfg.num_kv_heads * hd, dtype),
        "wv": _dense_init(kv, d, cfg.num_kv_heads * hd, dtype),
        "wo": _dense_init(ko, cfg.num_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def _attn_mask(S: int, T: int, causal: bool, window: int,
               q_offset: int) -> jnp.ndarray:
    """[S, T] boolean mask. T = total KV length; queries at q_offset..+S."""
    q_pos = jnp.arange(S)[:, None] + q_offset
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    return mask


# self-attention over S >= this many tokens takes the chunked jnp path
CHUNK_THRESHOLD = 8192


def _chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       causal: bool, window: int, *, q_chunk: int = 512,
                       kv_chunk: int = 1024) -> jnp.ndarray:
    """Pure-JAX flash attention: double scan over query/key chunks with
    running softmax stats — O(S) memory instead of O(S^2). Lowers on any
    backend (the Pallas kernel is the TPU-optimized twin).

    q: [B, S, H, D] (grouped/repeated to q heads already), k/v same H.
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    qc = min(q_chunk, S)
    kc = min(kv_chunk, T)
    nq, nk = S // qc, T // kc
    scale = 1.0 / math.sqrt(D)
    qs = jnp.moveaxis(q.reshape(B, nq, qc, H, D), 1, 0)     # [nq,B,qc,H,D]
    ks = jnp.moveaxis(k.reshape(B, nk, kc, H, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nk, kc, H, D), 1, 0)

    def q_block(_, qi_q):
        qi, qb = qi_q                                        # qb [B,qc,H,D]
        q32 = qb.astype(jnp.float32)

        def kv_block(carry, ki_kv):
            m, l, acc = carry
            ki, kb, vb = ki_kv
            logits = jnp.einsum("bqhd,bkhd->bhqk", q32,
                                kb.astype(jnp.float32)) * scale
            q_pos = qi * qc + jax.lax.broadcasted_iota(
                jnp.int32, (qc, kc), 0)
            k_pos = ki * kc + jax.lax.broadcasted_iota(
                jnp.int32, (qc, kc), 1)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= k_pos <= q_pos
            if window:
                mask &= k_pos > q_pos - window
            logits = jnp.where(mask[None, None], logits, -1e30)
            m_new = jnp.maximum(m, jnp.max(logits, -1))      # [B,H,qc]
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + jnp.sum(pr, -1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", pr, vb.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # [B,H,qc,D]
        return None, jnp.moveaxis(out, 1, 2).astype(q.dtype)  # [B,qc,H,D]

    _, blocks_out = jax.lax.scan(q_block, None, (jnp.arange(nq), qs))
    return jnp.moveaxis(blocks_out, 0, 1).reshape(B, S, H, D)


def attention(cfg: ModelConfig, p: Params, x: jnp.ndarray,
              positions: jnp.ndarray,
              kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cache_len: Optional[jnp.ndarray] = None,
              window: int = 0,
              use_kernels: bool = False,
              return_kv: bool = False) -> Tuple[jnp.ndarray, Optional[Tuple]]:
    """GQA attention. x: [B, S, d].

    Training/prefill: kv_cache is None -> self attention over x; with
    `return_kv` the new_cache is this call's (k, v) as [B, S, Hkv * D].
    Decode: kv_cache = (k, v) with [B, T, Hkv * D] (`lm.init_cache`'s
    layout, each position's KV heads side by side); x is the new token(s);
    `cache_len` [B] gives the valid prefix length. Returns (out, new_cache).
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    with jax.named_scope(scopes.QKV):
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(B, S, Hq, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    # sharding hints: shard heads over "model" when divisible, else fall
    # back to sharding the sequence (keeps 28/40-head configs from
    # replicating S x S logits on every chip)
    dp = hints.batch_spec_axes()
    msize = hints.axis_size("model")
    head_ok = msize > 1 and Hq % msize == 0
    kv_ok = msize > 1 and Hkv % msize == 0
    if head_ok:
        q = hints.constrain(q, dp, None, "model", None)
        k = hints.constrain(k, dp, None, "model" if kv_ok else None, None)
        v = hints.constrain(v, dp, None, "model" if kv_ok else None, None)
    else:
        q = hints.constrain(q, dp, "model", None, None)
        k = hints.constrain(k, dp, None, None, None)
        v = hints.constrain(v, dp, None, None, None)

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache                              # [B, T, Hkv * D]
        T = ck.shape[1]
        # scatter the new tokens at cache_len (decode: S == 1 typically)
        idx = (cache_len[:, None] + jnp.arange(S)[None, :])  # [B, S]
        with jax.named_scope(scopes.KV_CACHE_WRITE):
            bidx = jnp.arange(B)[:, None]
            ck = ck.at[bidx, idx].set(_flat_heads(k).astype(ck.dtype))
            cv = cv.at[bidx, idx].set(_flat_heads(v).astype(cv.dtype))
        new_cache = (ck, cv)
        if use_kernels and S == 1 and window == 0:
            from repro.kernels import ops as kops
            out = kops.paged_attention(q[:, 0], ck, cv, cache_len + S)
            return _out_proj(p, out[:, None]), new_cache
        k_all = ck.reshape(B, T, Hkv, hd)
        v_all = cv.reshape(B, T, Hkv, hd)
        # valid-key mask (+ causal within the new tokens + window)
        k_pos = jnp.arange(T)[None, None, :]                   # [1,1,T]
        q_pos = idx[:, :, None]                                # [B,S,1]
        mask = k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        mask = mask[:, None]                                   # [B,1,S,T]
    else:
        k_all, v_all = k, v
        T = S
        if return_kv:
            new_cache = (_flat_heads(k), _flat_heads(v))
        if use_kernels and cfg.causal and S >= 128:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True, window=window)
            return _out_proj(p, out), new_cache
        if S >= CHUNK_THRESHOLD:
            rep = Hq // Hkv
            out = _chunked_attention(q, jnp.repeat(k, rep, axis=2),
                                     jnp.repeat(v, rep, axis=2),
                                     cfg.causal, window)
            return _out_proj(p, out), new_cache
        mask = _attn_mask(S, T, cfg.causal, window, 0)[None, None]

    # grouped heads: repeat kv
    rep = Hq // Hkv
    k_all = jnp.repeat(k_all, rep, axis=2)
    v_all = jnp.repeat(v_all, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bshd,bthd->bhst", q, k_all) * scale
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v_all)
    return _out_proj(p, out), new_cache


def _flat_heads(x: jnp.ndarray) -> jnp.ndarray:
    """Heads [B, S, H, D] -> [B, S, H * D], the KV cache's layout."""
    return x.reshape(x.shape[:2] + (-1,))


def _out_proj(p: Params, out: jnp.ndarray) -> jnp.ndarray:
    """Heads [B, S, H, D] -> [B, S, d] through `wo`."""
    with jax.named_scope(scopes.OUT_PROJ):
        return _flat_heads(out) @ p["wo"]


def ring_attention_step(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                        positions: jnp.ndarray, ck: jnp.ndarray,
                        cv: jnp.ndarray, cache_len: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Sliding-window decode with a ring-buffered KV cache.

    x: [B, 1, d]; ck/cv: [B, W, Hkv * D] hold the last W tokens' K/V
    (already roped at their absolute positions); cache_len: [B] tokens seen
    so far.
    """
    Bsz, S, d = x.shape
    assert S == 1
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.num_heads, cfg.num_kv_heads
    Wn = ck.shape[1]
    with jax.named_scope(scopes.QKV):
        q = x @ p["wq"]
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = apply_rope(q.reshape(Bsz, 1, Hq, hd), positions, cfg.rope_theta,
                       cfg.mrope_sections)
        k = apply_rope(k.reshape(Bsz, 1, Hkv, hd), positions, cfg.rope_theta,
                       cfg.mrope_sections)
    with jax.named_scope(scopes.KV_CACHE_WRITE):
        slot = cache_len % Wn                               # [B]
        bidx = jnp.arange(Bsz)
        ck = ck.at[bidx, slot].set(_flat_heads(k)[:, 0].astype(ck.dtype))
        cv = cv.at[bidx, slot].set(v[:, 0].astype(cv.dtype))
    valid = jnp.arange(Wn)[None, :] <= jnp.minimum(cache_len, Wn - 1)[:, None]
    rep = Hq // Hkv
    k_all = jnp.repeat(ck.reshape(Bsz, Wn, Hkv, hd), rep, axis=2)
    v_all = jnp.repeat(cv.reshape(Bsz, Wn, Hkv, hd), rep, axis=2)
    logits = jnp.einsum("bshd,bthd->bhst", q, k_all) / math.sqrt(hd)
    logits = jnp.where(valid[:, None, None, :], logits.astype(jnp.float32),
                       -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v_all)
    return _out_proj(p, out), (ck, cv)


# ---------------------------------------------------------------------- MLP
def init_mlp(cfg: ModelConfig, key, dtype=jnp.float32,
             d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.activation in ("swiglu", "geglu"):
        return {"w_gate": _dense_init(k1, d, ff, dtype),
                "w_up": _dense_init(k2, d, ff, dtype),
                "w_down": _dense_init(k3, ff, d, dtype)}
    return {"w_up": _dense_init(k1, d, ff, dtype),
            "w_down": _dense_init(k2, ff, d, dtype)}


def apply_mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if cfg.activation == "geglu":
        return (jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"]) @ p["w_down"]
