"""Plain reference of the Qwen2 decoder (Qwen2ForCausalLM) in float32.

Follows the published architecture: token embedding; per layer RMSNorm,
GQA attention with q/k/v bias and rotary embedding (rotate-half, base
`rope_theta`), output projection, residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm; a head tied to the embedding or, where
`tie_word_embeddings` is false, its own `lm_head` ([hidden, vocab]). It
imports nothing of the program under test and makes its own weights from
the seed.

Every matmul runs at `Precision.HIGHEST` in float32, from the weights as
served (bf16, upcast one layer at a time inside the scan). `quant="fp8"`
is the control: the same forward with each linear layer's operands
rounded to float8_e4m3fn (weights per output column, activations per
row), the precision below bf16 that a later change might try.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words of a threefry key, from any non-negative seed."""
    return np.random.SeedSequence(seed).generate_state(2).astype(np.uint32)


def _sizes(cfg: dict) -> Dict[str, int]:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, L=cfg["num_hidden_layers"], hq=hq,
                hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // hq,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"])


def weight_shapes(cfg: dict) -> Dict[str, tuple]:
    s = _sizes(cfg)
    d, L, hq, hkv, hd, ff = (s[k] for k in ("d", "L", "hq", "hkv", "hd",
                                            "ff"))
    shapes = {"embed": (s["V"], d),
              "q_w": (L, d, hq * hd), "q_b": (L, hq * hd),
              "k_w": (L, d, hkv * hd), "k_b": (L, hkv * hd),
              "v_w": (L, d, hkv * hd), "v_b": (L, hkv * hd),
              "o_w": (L, hq * hd, d),
              "gate_w": (L, d, ff), "up_w": (L, d, ff), "down_w": (L, ff, d),
              "ln1": (L, d), "ln2": (L, d), "norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (d, s["V"])
    return shapes


def init_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights from the seed, made on the device in one jitted call:
    matrices in `dtype` with variance 1/fan_in (the embedding 0.02), biases
    of scale 0.1 in `dtype`, norm scales 1 + 0.1·N(0, 1) in float32."""
    shapes = weight_shapes(cfg)

    def make(kd):
        key = jax.random.wrap_key_data(kd)
        out = {}
        for i, (name, shp) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name in ("ln1", "ln2", "norm"):
                out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
            elif name.endswith("_b"):
                out[name] = (0.1 * jax.random.normal(k, shp, jnp.float32)
                             ).astype(dtype)
            elif name == "embed":
                out[name] = (0.02 * jax.random.normal(k, shp, jnp.float32)
                             ).astype(dtype)
            else:
                out[name] = (jax.random.normal(k, shp, jnp.float32)
                             / math.sqrt(shp[-2])).astype(dtype)
        return out

    return jax.jit(make)(key_data(seed))


# -------------------------------------------------------------- arithmetic
def _q8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8_e4m3fn with one scale per slice along `axis`."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x: jnp.ndarray, w: jnp.ndarray, quant: str) -> jnp.ndarray:
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x [N, T, H, D], positions 0..T-1, rotate-half convention."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float64) / D))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg: dict, x: jnp.ndarray, w: dict, quant: str) -> jnp.ndarray:
    s = _sizes(cfg)
    N, T, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, w["ln1"], eps)
    q = _linear(h, w["q_w"], quant) + w["q_b"].astype(jnp.float32)
    k = _linear(h, w["k_w"], quant) + w["k_b"].astype(jnp.float32)
    v = _linear(h, w["v_w"], quant) + w["v_b"].astype(jnp.float32)
    q = _rope(q.reshape(N, T, s["hq"], s["hd"]), theta)
    k = _rope(k.reshape(N, T, s["hkv"], s["hd"]), theta)
    v = v.reshape(N, T, s["hkv"], s["hd"])
    g = s["hq"] // s["hkv"]
    k = jnp.repeat(k, g, axis=2)          # query head i reads kv head i // g
    v = jnp.repeat(v, g, axis=2)
    logits = jnp.einsum("nshd,nthd->nhst", q, k, precision=HI) / math.sqrt(
        s["hd"])
    causal = np.tril(np.ones((T, T), bool))
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("nhst,nthd->nshd", p, v, precision=HI)
    x = x + _linear(o.reshape(N, T, s["hq"] * s["hd"]), w["o_w"], quant)
    h = _rms(x, w["ln2"], eps)
    mlp = jax.nn.silu(_linear(h, w["gate_w"], quant)) * _linear(
        h, w["up_w"], quant)
    return x + _linear(mlp, w["down_w"], quant)


ARCH_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "intermediate_size", "vocab_size",
             "num_hidden_layers", "rms_norm_eps", "rope_theta",
             "tie_word_embeddings")
LAYER_KEYS = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "gate_w",
              "up_w", "down_w", "ln1", "ln2")


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _hidden(cfg_items, w, tokens, quant):
    cfg = dict(cfg_items)
    x = w["embed"].astype(jnp.float32)[tokens]

    def body(x, lw):
        return _layer(cfg, x, lw, quant), None

    x, _ = jax.lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    return _rms(x, w["norm"], cfg["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_gaps(head, h, chosen, h_ctrl, quant):
    """Per sequence: reference logits at each position (`head` is
    [vocab, hidden]); the gap of the chosen token below the best, and of the
    control's first choice."""
    def one(args):
        hs, ch, hc = args
        e = head.astype(jnp.float32)
        ref = jnp.matmul(hs, e.T, precision=HI)                 # [P, V]
        best = jnp.max(ref, -1)
        got = jnp.take_along_axis(ref, ch[:, None], -1)[:, 0]
        if hc is None:
            return best - got, jnp.zeros_like(best)
        ctrl = _linear(hc, e.T, quant)
        pick = jnp.argmax(ctrl, -1)
        alt = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return best - got, best - alt

    return jax.lax.map(one, (h, chosen, h_ctrl))


def served_gaps(cfg: dict, w: dict, prompts: np.ndarray, served: np.ndarray,
                control: bool = False):
    """prompts [N, S] and the served tokens [N, n] that followed them.

    Runs the reference once over each prompt with its served tokens and
    returns, per served token, how far its reference logit lies below the
    reference's best at that position ([N, n]). With `control`, also the
    gap of the token that the fp8 forward puts first at each position.
    """
    S = prompts.shape[1]
    seq = np.concatenate([prompts, served], axis=1)[:, :-1].astype(np.int32)
    items = tuple(sorted((k, v) for k, v in cfg.items() if k in ARCH_KEYS))
    h = _hidden(items, w, jnp.asarray(seq), "f32")[:, S - 1:]
    hc = (_hidden(items, w, jnp.asarray(seq), "fp8")[:, S - 1:]
          if control else None)
    head = w["embed"] if cfg["tie_word_embeddings"] else w["lm_head"].T
    gap, ctrl = _head_gaps(head, h, jnp.asarray(served, jnp.int32),
                           hc, "fp8" if control else "f32")
    return np.asarray(gap), (np.asarray(ctrl) if control else None)
