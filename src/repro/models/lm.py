"""Model assembly: embedding -> scanned block stack -> head, for all ten
assigned architectures, with train (full-sequence), prefill (stateful), and
decode (single-token, cached) paths.

Layer stacking: the block pattern (e.g. RecurrentGemma's
(rglru, rglru, local)) repeats every `period` layers. The stack is scanned
over *periods* — `num_layers // period` iterations of a body holding one
instance of each pattern position — which keeps HLO size O(period) while
supporting heterogeneous stacks. Remainder layers (38 = 12*3 + 2) run
unrolled. Homogeneous models degenerate to the classic scan-over-layers.

Caches ride the scan as per-period xs/ys; each pattern position owns a
kind-specific cache (attention KV / RG-LRU h+conv / RWKV6 state).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import scopes
from repro.configs.base import (BLOCK_FULL, BLOCK_LOCAL, BLOCK_RGLRU,
                                BLOCK_RWKV6, ModelConfig)
from repro.models import blocks as B
from repro.models import frontends as F
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import rwkv6 as W
from repro.runtime import hints

Params = Dict[str, Any]

# Execution knobs (perf iterations mutate these)
LM_CONFIG = {"seq_parallel_residual": 0}   # 1 -> Korthikanti-style SP


# ==================================================================== init
def _init_layer(cfg: ModelConfig, kind: str, key, dtype) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": B.init_norm(cfg, cfg.d_model),
                 "norm2": B.init_norm(cfg, cfg.d_model)}
    if kind in (BLOCK_FULL, BLOCK_LOCAL):
        p["mix"] = B.init_attention(cfg, k1, dtype)
    elif kind == BLOCK_RGLRU:
        p["mix"] = R.init_rglru(cfg, k1, dtype)
    elif kind == BLOCK_RWKV6:
        p["mix"] = W.init_rwkv6(cfg, k1, dtype)
    else:
        raise ValueError(kind)
    if cfg.moe is not None:
        p["ffn"] = M.init_moe(cfg, k2, dtype)
    else:
        p["ffn"] = B.init_mlp(cfg, k2, dtype)
    return p


def _init_period(cfg: ModelConfig, key, dtype) -> Tuple[Params, ...]:
    keys = jax.random.split(key, len(cfg.block_pattern))
    return tuple(_init_layer(cfg, kind, k, dtype)
                 for kind, k in zip(cfg.block_pattern, keys))


def init_model(cfg: ModelConfig, key, dtype=jnp.float32) -> Params:
    period = len(cfg.block_pattern)
    n_periods, n_tail = divmod(cfg.num_layers, period)
    ks = jax.random.split(key, 6)
    params: Params = {}
    if cfg.frontend is None or cfg.frontend.kind == "vision":
        emb = (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                 jnp.float32) * 0.02).astype(dtype)
        params["embed"] = emb
    if cfg.frontend is not None:
        params["frontend"] = F.init_frontend(cfg, ks[1], dtype)
    if n_periods:
        pkeys = jax.random.split(ks[2], n_periods)
        params["scan"] = jax.vmap(
            lambda k: _init_period(cfg, k, dtype))(pkeys)
    if n_tail:
        tkeys = jax.random.split(ks[3], n_tail)
        params["tail"] = [
            _init_layer(cfg, cfg.block_pattern[i % period], tkeys[i], dtype)
            for i in range(n_tail)]
    params["final_norm"] = B.init_norm(cfg, cfg.d_model)
    if not cfg.tie_embeddings:
        params["head"] = B._dense_init(ks[4], cfg.d_model, cfg.vocab_size,
                                       dtype)
    return params


# =================================================================== caches
def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Params:
    """Attention K and V are [batch, len, Hkv * D], each position's KV heads
    side by side: the layout the paged kernel reads, so decode never relays
    the cache."""
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    if kind == BLOCK_FULL:
        return {"k": jnp.zeros((batch, max_len, width), dtype),
                "v": jnp.zeros((batch, max_len, width), dtype)}
    if kind == BLOCK_LOCAL:
        w = min(cfg.window_size or max_len, max_len)
        return {"k": jnp.zeros((batch, w, width), dtype),
                "v": jnp.zeros((batch, w, width), dtype)}
    if kind == BLOCK_RGLRU:
        return R.init_rglru_state(cfg, batch)
    if kind == BLOCK_RWKV6:
        return W.init_rwkv6_state(cfg, batch)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    """Decode cache pytree: {"scan": leaves [P, ...], "tail": [...],
    "len": [B]} — `len` is the shared valid-prefix length."""
    period = len(cfg.block_pattern)
    n_periods, n_tail = divmod(cfg.num_layers, period)
    cache: Params = {"len": jnp.zeros((batch,), jnp.int32)}
    if n_periods:
        one = tuple(init_layer_cache(cfg, kind, batch, max_len, dtype)
                    for kind in cfg.block_pattern)
        cache["scan"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape), one)
    if n_tail:
        cache["tail"] = [init_layer_cache(cfg, cfg.block_pattern[i % period],
                                          batch, max_len, dtype)
                         for i in range(n_tail)]
    return cache


# =================================================================== layers
# Every layer of the step programs is named with `jax.named_scope`, which
# sets the compiled instructions' `op_name` metadata and nothing else; the
# names and their nesting are in `repro.scopes`.


def _apply_mixer(cfg: ModelConfig, kind: str, p: Params, h: jnp.ndarray,
                 positions: jnp.ndarray, cache: Optional[Params],
                 cache_len: Optional[jnp.ndarray], use_kernels: bool
                 ) -> Tuple[jnp.ndarray, Optional[Params]]:
    if kind == BLOCK_RGLRU:
        return R.apply_rglru(cfg, p, h, cache)
    if kind == BLOCK_RWKV6:
        return W.apply_rwkv6(cfg, p, h, cache)
    window = cfg.window_size if kind == BLOCK_LOCAL else 0
    if cache is None:
        out, _ = B.attention(cfg, p, h, positions, window=window,
                             use_kernels=use_kernels)
        return out, None
    if kind == BLOCK_LOCAL and h.shape[1] == 1:
        # decode through the ring-buffered window cache
        out, nc = B.ring_attention_step(cfg, p, h, positions, cache["k"],
                                        cache["v"], cache_len)
    elif kind == BLOCK_LOCAL:
        # windowed prefill; ring-fill the cache with the last W
        # tokens (slot = absolute position mod W)
        out, kv = B.attention(cfg, p, h, positions, window=window,
                              use_kernels=use_kernels, return_kv=True)
        Wn = cache["k"].shape[1]
        S = h.shape[1]
        take = min(Wn, S)
        with jax.named_scope(scopes.KV_CACHE_WRITE):
            slots = (jnp.arange(S - take, S)) % Wn
            nc = (cache["k"].at[:, slots].set(
                      kv[0][:, -take:].astype(cache["k"].dtype)),
                  cache["v"].at[:, slots].set(
                      kv[1][:, -take:].astype(cache["v"].dtype)))
    elif h.shape[1] > 1:
        # full-attention prefill: run self-attention (chunked for
        # long S) and bulk-fill the cache prefix — avoids the
        # [S, T_max] masked-cache path entirely.
        out, kv = B.attention(cfg, p, h, positions, window=window,
                              use_kernels=use_kernels, return_kv=True)
        S = h.shape[1]
        with jax.named_scope(scopes.KV_CACHE_WRITE):
            nc = (cache["k"].at[:, :S].set(kv[0].astype(cache["k"].dtype)),
                  cache["v"].at[:, :S].set(kv[1].astype(cache["v"].dtype)))
    else:
        out, nc = B.attention(cfg, p, h, positions,
                              kv_cache=(cache["k"], cache["v"]),
                              cache_len=cache_len,
                              window=window, use_kernels=use_kernels)
    return out, {"k": nc[0], "v": nc[1]}


def _apply_layer(cfg: ModelConfig, kind: str, p: Params, x: jnp.ndarray,
                 positions: jnp.ndarray, cache: Optional[Params],
                 cache_len: Optional[jnp.ndarray], use_kernels: bool,
                 moe_mode: str) -> Tuple[jnp.ndarray, Optional[Params],
                                         jnp.ndarray]:
    if LM_CONFIG["seq_parallel_residual"] and x.shape[1] > 1:
        # sequence-parallel residual stream: norms/elementwise run with S
        # sharded over "model"; XLA all-gathers S at the matmul boundaries
        # and reduce-scatters the outputs (halves activation-collective
        # volume vs all-reduce and shards the residual/norm memory).
        x = hints.constrain(x, hints.batch_spec_axes(), "model", None)
    with jax.named_scope(scopes.MIXER[kind]):
        h = B.apply_norm(cfg, p["norm1"], x)
        out, new_cache = _apply_mixer(cfg, kind, p["mix"], h, positions,
                                      cache, cache_len, use_kernels)
        x = x + out
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope(scopes.MLP if cfg.moe is None else scopes.MOE):
        h2 = B.apply_norm(cfg, p["norm2"], x)
        if cfg.moe is not None:
            ffn_out, aux = M.apply_moe(cfg, p["ffn"], h2, mode=moe_mode)
        else:
            ffn_out = B.apply_mlp(cfg, p["ffn"], h2)
        return x + ffn_out, new_cache, aux


# ================================================================== forward
def _remat_wrap(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "selective":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def forward_blocks(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                   positions: jnp.ndarray, cache: Optional[Params] = None,
                   use_kernels: bool = False, moe_mode: str = "capacity",
                   remat: str = "none"
                   ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    period = len(cfg.block_pattern)
    n_periods, n_tail = divmod(cfg.num_layers, period)
    cache_len = cache["len"] if cache is not None else None
    new_cache: Params = {} if cache is not None else None
    aux_total = jnp.zeros((), jnp.float32)

    def period_body(carry, xs):
        xc, aux = carry
        pparams, pcache = xs
        ncaches = []
        for j, kind in enumerate(cfg.block_pattern):
            c_j = None if pcache is None else pcache[j]
            xc, nc, a = _apply_layer(cfg, kind, pparams[j], xc, positions,
                                     c_j, cache_len, use_kernels, moe_mode)
            ncaches.append(nc)
            aux = aux + a
        out_caches = tuple(ncaches) if pcache is not None else None
        return (xc, aux), out_caches

    if n_periods:
        body = _remat_wrap(period_body, remat)
        scan_cache = cache["scan"] if cache is not None else None
        with jax.named_scope(scopes.LAYERS):
            (x, aux_total), updated = jax.lax.scan(
                body, (x, aux_total),
                (params["scan"], scan_cache))
        if cache is not None:
            new_cache["scan"] = updated
    if n_tail:
        tail_caches = []
        for i in range(n_tail):
            kind = cfg.block_pattern[i % period]
            c_i = cache["tail"][i] if cache is not None else None
            with jax.named_scope(scopes.LAYERS):
                x, nc, a = _apply_layer(cfg, kind, params["tail"][i], x,
                                        positions, c_i, cache_len,
                                        use_kernels, moe_mode)
                aux_total = aux_total + a
            tail_caches.append(nc)
        if cache is not None:
            new_cache["tail"] = tail_caches
    return x, new_cache, aux_total


def embed_inputs(cfg: ModelConfig, params: Params, inputs: Dict[str, Any],
                 dtype=jnp.bfloat16) -> jnp.ndarray:
    """tokens/features -> [B, S, d] stream."""
    with jax.named_scope(scopes.EMBED):
        if cfg.frontend is not None and cfg.frontend.kind == "audio":
            return F.apply_audio_features(
                cfg, params["frontend"], inputs["features"].astype(dtype))
        x = params["embed"].astype(dtype)[inputs["tokens"]]
        if cfg.frontend is not None and cfg.frontend.kind == "vision":
            x = F.apply_vision_prefix(cfg, params["frontend"], x,
                                      inputs["vision_embeds"])
        return x


def positions_for(cfg: ModelConfig, batch: int, seq: int,
                  offset=0) -> jnp.ndarray:
    if cfg.mrope_sections:
        return F.mrope_positions(cfg, batch, seq, offset)
    pos = jnp.arange(seq)[None, :] + (
        offset if isinstance(offset, int) else offset[:, None])
    return jnp.broadcast_to(pos, (batch, seq)) if pos.shape[0] == 1 else pos


def _head_logits(cfg: ModelConfig, params: Params,
                 x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return x @ params["embed"].astype(x.dtype).T
    return x @ params["head"]


def chunked_xent(cfg: ModelConfig, params: Params, x: jnp.ndarray,
                 labels: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy without materializing full [B, S, V] logits: scan over
    sequence chunks with rematerialization (the logits are recomputed in the
    backward pass chunk by chunk)."""
    Bsz, S, d = x.shape
    chunk = min(chunk, S)
    n = S // chunk
    xc = x[:, :n * chunk].reshape(Bsz, n, chunk, d).swapaxes(0, 1)
    lc = labels[:, :n * chunk].reshape(Bsz, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(carry, xs):
        xm, lm = xs
        logits = _head_logits(cfg, params, xm).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lm[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (Bsz * n * chunk)


def cast_params_for_compute(params: Params, dtype=jnp.bfloat16) -> Params:
    """Cast >=2D float32 weights to the compute dtype (master copies stay in
    the optimizer); 1D scales/biases and integer leaves keep their dtype."""
    def cast(t):
        if isinstance(t, jnp.ndarray) and t.dtype == jnp.float32 and t.ndim >= 2:
            return t.astype(dtype)
        return t
    return jax.tree.map(cast, params)


# ============================================================== entrypoints
def train_loss(cfg: ModelConfig, params: Params, inputs: Dict[str, Any],
               use_kernels: bool = False, moe_mode: str = "capacity",
               remat: str = "selective",
               dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full-sequence LM (or masked-frame) loss."""
    params = cast_params_for_compute(params, dtype)
    x = embed_inputs(cfg, params, inputs, dtype)
    Bsz, S = x.shape[:2]
    positions = positions_for(cfg, Bsz, S)
    x, _, aux = forward_blocks(cfg, params, x, positions, None,
                               use_kernels, moe_mode, remat)
    with jax.named_scope(scopes.HEAD):
        x = B.apply_norm(cfg, params["final_norm"], x)
        loss = chunked_xent(cfg, params, x, inputs["labels"])
    if cfg.moe is not None:
        loss = loss + cfg.moe.load_balance_loss_weight * aux / cfg.num_layers
    return loss, {"aux_loss": aux}


def prefill(cfg: ModelConfig, params: Params, inputs: Dict[str, Any],
            cache: Params, use_kernels: bool = False,
            moe_mode: str = "capacity",
            dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Encoder forward / decoder prefill: returns last-position logits and a
    filled cache (for decoders)."""
    params = cast_params_for_compute(params, dtype)
    x = embed_inputs(cfg, params, inputs, dtype)
    Bsz, S = x.shape[:2]
    positions = positions_for(cfg, Bsz, S)
    x, new_cache, _ = forward_blocks(cfg, params, x, positions,
                                     cache if cfg.is_decoder else None,
                                     use_kernels, moe_mode)
    with jax.named_scope(scopes.HEAD):
        x = B.apply_norm(cfg, params["final_norm"], x)
        logits = _head_logits(cfg, params, x[:, -1:])
    if new_cache is not None:
        new_cache["len"] = cache["len"] + S
    return logits, new_cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, use_kernels: bool = False,
                moe_mode: str = "capacity",
                dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """One decode step: tokens [B, 1] + cache -> logits [B, 1, V] + cache."""
    params = cast_params_for_compute(params, dtype)
    with jax.named_scope(scopes.EMBED):
        x = params["embed"][tokens]
    Bsz = x.shape[0]
    positions = positions_for(cfg, Bsz, 1, offset=cache["len"])
    x, new_cache, _ = forward_blocks(cfg, params, x, positions, cache,
                                     use_kernels, moe_mode)
    with jax.named_scope(scopes.HEAD):
        x = B.apply_norm(cfg, params["final_norm"], x)
        logits = _head_logits(cfg, params, x)
    new_cache["len"] = cache["len"] + 1
    return logits, new_cache
