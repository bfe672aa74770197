"""Pure-jnp oracles for every Pallas kernel. Tests assert_allclose each
kernel (interpret=True) against these."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def gather_ref(table: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """async_gather oracle: out[i] = table[indices[i]]."""
    return table[indices]


def scatter_update_ref(table: jnp.ndarray, indices: jnp.ndarray,
                       updates: jnp.ndarray, op: str = "add") -> jnp.ndarray:
    """async_scatter oracle: read-modify-write, conflicts serialized in
    index order (both add and xor commute, so any serialization matches)."""
    if op == "add":
        return table.at[indices].add(updates)
    if op == "xor":
        def body(i, t):
            return t.at[indices[i]].set(t[indices[i]] ^ updates[i])
        return jax.lax.fori_loop(0, indices.shape[0], body, table)
    raise ValueError(op)


def triad_ref(b: jnp.ndarray, c: jnp.ndarray, s: float) -> jnp.ndarray:
    """STREAM triad oracle: a = b + s * c."""
    return b + s * c


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: int = 0,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """flash_attention oracle. q: [B, Hq, S, D]; k/v: [B, Hkv, T, D].
    GQA: q head h attends kv head h // (Hq // Hkv)."""
    B, Hq, S, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scale = scale or 1.0 / math.sqrt(D)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(S)[:, None] + (T - S)   # queries at the sequence tail
    k_pos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """paged_attention (decode) oracle.
    q: [B, Hq, D]; caches: [B, T, Hkv * D]; lengths: [B] valid prefix."""
    B, Hq, D = q.shape
    T = k_cache.shape[1]
    k_cache = k_cache.reshape(B, T, -1, D)     # [B, T, Hkv, D]
    v_cache = v_cache.reshape(B, T, -1, D)
    rep = Hq // k_cache.shape[2]
    k = jnp.repeat(k_cache, rep, axis=2)       # [B, T, Hq, D]
    v = jnp.repeat(v_cache, rep, axis=2)
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(D)
    mask = jnp.arange(T)[None, :] < lengths[:, None]       # [B, T]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)
