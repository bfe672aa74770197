"""paged_attention — single-token decode attention with KV pages streamed
from HBM ("far memory") through the VMEM pipeline.

This is the serving-side AMU: at decode, the KV cache (32k-512k tokens) is
far memory touched once per token — no reuse, pure latency/bandwidth. The
kernel walks the cache page by page (page = `aload` granularity); the Pallas
grid pipeline keeps multiple page DMAs in flight while the MXU consumes the
previous page (issue/complete decoupling). Pages past the sequence length
are skipped via the scalar-prefetched `lengths`.

Layout: the cache is ``[B, T, W]`` with ``W = Hkv * D``, each position's KV
heads side by side in one row, as the model stores it (`lm.init_cache`),
and the kernel reads it as it stands. A page block ``(1, page, W)`` spans
every KV head and meets the TPU's (8, 128) block rule for any head_dim.
Queries come as ``[B, Hkv, G, D]``, the ``G = Hq / Hkv`` query heads of
each KV head. One grid step walks the KV heads: head ``h`` scores its
``[G, D]`` queries against the page's lanes ``[h*D, (h+1)*D)``, so each
matmul is as wide as one head, not the whole page.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, page: int, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(1)
    np_ = pl.num_programs(1)
    seq_len = len_ref[b]
    hkv, _, d = q_ref.shape[1:]

    @pl.when(pi == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = pi * page

    @pl.when(start < seq_len)
    def _():
        for h in range(hkv):
            lanes = pl.ds(h * d, d)
            q = q_ref[0, h].astype(jnp.float32)            # [G, D]
            k = k_ref[0, :, lanes].astype(jnp.float32)     # [page, D]
            v = v_ref[0, :, lanes].astype(jnp.float32)
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))) * scale    # [G, page]
            pos = start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(pos < seq_len, logits, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + p @ v        # [G, D]
            m_ref[h] = m_new

    @pl.when(pi == np_ - 1)
    def _():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page", "interpret"))
def paged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, lengths: jnp.ndarray,
                    page: int = 512, interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hq, D]; k_cache/v_cache: [B, T, Hkv * D]; lengths: [B] ->
    out [B, Hq, D]. Query head i reads KV head h = i // (Hq / Hkv): the
    lanes [h * D, (h + 1) * D) of each cache row."""
    B, Hq, D = q.shape
    T, W = k_cache.shape[1], k_cache.shape[2]
    Hkv = W // D
    assert Hkv * D == W and Hq % Hkv == 0, (Hq, W, D)
    G = Hq // Hkv
    page = min(page, T)
    assert T % page == 0, (T, page)
    scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(_paged_kernel, page=page, scale=scale)
    heads = pl.BlockSpec((1, Hkv, G, D), lambda b, pi, L: (b, 0, 0, 0))
    q_heads = q.reshape(B, Hkv, G, D)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T // page),
            in_specs=[
                heads,
                pl.BlockSpec((1, page, W), lambda b, pi, L: (b, pi, 0)),
                pl.BlockSpec((1, page, W), lambda b, pi, L: (b, pi, 0)),
            ],
            out_specs=heads,
            scratch_shapes=[
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, 1), jnp.float32),
                pltpu.VMEM((Hkv, G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(lengths, q_heads, k_cache, v_cache)
    return out.reshape(B, Hq, D)
