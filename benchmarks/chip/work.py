"""Operations and bytes that each kernel and each whole step requires,
computed from the shapes and the published configuration (its own keys, as
in the model's `config.json`), never from the program.

A kernel's count is of the work its algorithm needs, however the kernel is
implemented: decode attention reads q and the valid KV prefix of each
sequence (not the padded cache) and writes its output; causal flash
attention does half of the square and reads q, k and v once. A kernel that
fetches or computes more than that reads a lower share of its roofline.
Model FLOPs count every matmul once (2 per multiply-add) and no recompute.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def widths(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return {"d": d, "layers": cfg["num_hidden_layers"], "hq": hq,
            "hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // hq,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by in one layer: q, k, v, o, then the
    SwiGLU MLP."""
    w = widths(cfg)
    d, hd = w["d"], w["hd"]
    attn = d * w["hq"] * hd + 2 * d * w["hkv"] * hd + w["hq"] * hd * d
    return attn + 3 * d * w["ff"]


def head_params(cfg: dict) -> int:
    w = widths(cfg)
    return w["d"] * w["vocab"]


def causal_attention_flops(cfg: dict, batch: int, seq: int) -> int:
    """QK^T and PV over the lower triangle of one layer: half of
    4·B·Hq·hd·S²."""
    w = widths(cfg)
    return 2 * batch * w["hq"] * w["hd"] * seq * seq


def paged_attention(cfg: dict, lengths: Iterable[int]) -> Tuple[int, int]:
    """One decode-attention call (one layer, one step). `lengths` are the
    valid prefixes, the new token included."""
    w = widths(cfg)
    lens = list(lengths)
    total = sum(lens)
    flops = 4 * w["hq"] * w["hd"] * total
    qo = 2 * len(lens) * w["hq"] * w["hd"] * BF16
    kv = 2 * total * w["hkv"] * w["hd"] * BF16
    return flops, qo + kv


def flash_attention(cfg: dict, batch: int, seq: int) -> Tuple[int, int]:
    """One causal self-attention call (one layer) over `seq` tokens."""
    w = widths(cfg)
    flops = causal_attention_flops(cfg, batch, seq)
    qo = 2 * batch * seq * w["hq"] * w["hd"] * BF16
    kv = 2 * batch * seq * w["hkv"] * w["hd"] * BF16
    return flops, qo + kv


def prefill_flops(cfg: dict, batch: int, seq: int) -> int:
    """Prefill of `batch` prompts of `seq` tokens; the head runs on the last
    position only."""
    w = widths(cfg)
    return (2 * batch * seq * w["layers"] * layer_matmul_params(cfg)
            + w["layers"] * causal_attention_flops(cfg, batch, seq)
            + 2 * batch * head_params(cfg))


def decode_flops(cfg: dict, lengths: Iterable[int]) -> int:
    """One decode step of len(lengths) sequences, attention over each valid
    prefix (the new token included)."""
    w = widths(cfg)
    lens = list(lengths)
    per_token = layer_matmul_params(cfg) * w["layers"] + head_params(cfg)
    attn = w["layers"] * 4 * w["hq"] * w["hd"] * sum(lens)
    return 2 * len(lens) * per_token + attn


def roofline_s(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["flops_bf16"]
    t_b = nbytes / peaks["hbm_bytes_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
