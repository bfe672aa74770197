"""Sharding rules: PartitionSpecs for params, optimizer state, caches, and
batches on the production meshes ("pod", "data", "model").

Conventions
-----------
* DP/batch: ("pod", "data") — gradients all-reduce across both axes.
* TP: "model" — attention heads / FFN hidden / vocab / experts.
* FSDP (big models): params additionally sharded over "data" on the largest
  non-TP dimension; XLA SPMD inserts the per-layer all-gather inside the
  scan and reduce-scatters the grads.
* ZeRO-1: optimizer moments follow the FSDP spec even when params are
  replicated (zero1 flag) — each data shard owns a slice of m/v.
* Sequence parallel: for prefill/long-context cells whose batch cannot
  cover the data axis, the sequence dimension shards over "data".

Leaf classification is name-based over the param pytree paths, mirroring
how production frameworks (MaxText et al.) declare logical axis rules.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (ModelConfig, ParallelConfig,
                                ShapeConfig)

Params = Any


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _divisible(n: int, mesh: Mesh, *axes: str) -> bool:
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return n % size == 0


def param_spec(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
               path: str, leaf) -> P:
    """PartitionSpec for one parameter leaf (path from tree_flatten_with_path).

    Stacked scan params have a leading period/layer axis that is never
    sharded; the rules below address the trailing dims.
    """
    dp = batch_axes(mesh)
    shape = leaf.shape
    nd = len(shape)

    def fsdp_axis(tp_dim: Optional[int]) -> Optional[int]:
        """Pick the largest non-TP trailing dim divisible by the data axes."""
        if not par.fsdp:
            return None
        best, best_dim = None, 0
        start = 1 if nd >= 3 else 0      # skip the stacked layer axis
        for i in range(start, nd):
            if i == tp_dim:
                continue
            if shape[i] > best_dim and _divisible(shape[i], mesh, *dp):
                best, best_dim = i, shape[i]
        return best

    def spec_with(tp_dim: Optional[int]) -> P:
        axes = [None] * nd
        if tp_dim is not None and _divisible(shape[tp_dim], mesh, "model"):
            axes[tp_dim] = "model"
        else:
            tp_dim = None
        fa = fsdp_axis(tp_dim)
        if fa is not None:
            axes[fa] = dp if len(dp) > 1 else dp[0]
        return P(*axes)

    # ---- embedding / head: vocab over model --------------------------------
    if re.search(r"\['embed'\]|\['head'\]", path):
        vocab_dim = next((i for i, s in enumerate(shape)
                          if s == cfg.vocab_size), 0)
        return spec_with(vocab_dim)
    # ---- MoE experts: expert dim over model (expert parallelism) -----------
    if re.search(r"\['ffn'\].*\['(w_gate|w_up|w_down)'\]", path) \
            and cfg.moe is not None and nd >= 3:
        axes = [None] * nd
        e_dim = nd - 3                   # [..., E, in, out]
        if par.expert_parallel and _divisible(shape[e_dim], mesh, "model"):
            axes[e_dim] = "model"
            if par.fsdp and _divisible(shape[nd - 1], mesh, *dp):
                axes[nd - 1] = dp if len(dp) > 1 else dp[0]
        return P(*axes)
    if "router" in path:
        return P(*([None] * nd))
    # ---- attention projections: heads (fused out dim) over model -----------
    if re.search(r"\['mix'\].*\['w(q|k|v)'\]", path):
        return spec_with(nd - 1)
    if re.search(r"\['mix'\].*\['wo'\]", path):
        return spec_with(nd - 2)         # input dim = heads*hd
    if re.search(r"\['mix'\].*\['b(q|k|v)'\]", path):
        axes = [None] * nd
        if _divisible(shape[-1], mesh, "model"):
            axes[-1] = "model"
        return P(*axes)
    # ---- recurrent mixers: width over model ---------------------------------
    if re.search(r"\['mix'\].*\['(w_x|w_y|w_r|w_i|w_k|w_v|w_g|w_w)'\]", path):
        return spec_with(nd - 1)
    if re.search(r"\['mix'\].*\['(w_out|w_o)'\]", path):
        return spec_with(nd - 2)
    # ---- MLP: hidden over model ---------------------------------------------
    if re.search(r"\['ffn'\].*\['(w_gate|w_up)'\]", path):
        return spec_with(nd - 1)
    if re.search(r"\['ffn'\].*\['w_down'\]", path):
        return spec_with(nd - 2)
    # ---- everything else (norms, small vectors): replicated ----------------
    return P(*([None] * nd))


def params_shardings(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                     params: Params) -> Params:
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        spec = param_spec(cfg, par, mesh, jax.tree_util.keystr(path), leaf)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_shardings(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                        params: Params) -> Dict[str, Any]:
    """ZeRO-1: moments follow FSDP placement even if params replicate."""
    zpar = par if par.fsdp else (
        ParallelConfig(**{**par.__dict__, "fsdp": par.zero1}))
    m = params_shardings(cfg, zpar, mesh, params)
    return {"m": m, "v": m,
            "step": NamedSharding(mesh, P())}


def batch_shardings(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                    shape: ShapeConfig) -> Dict[str, NamedSharding]:
    dp = batch_axes(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    B = shape.global_batch
    dsize = int(np.prod([mesh.shape[a] for a in dp]))
    shard_batch = B % dsize == 0
    seq_axis = None
    if (par.seq_shard and not shard_batch and shape.kind == "prefill"
            and shape.seq_len % mesh.shape["model"] == 0):
        seq_axis = "model"
    b_axis = dp_spec if shard_batch else None

    def ns(*axes):
        return NamedSharding(mesh, P(*axes))

    out = {
        "tokens": ns(b_axis, seq_axis),
        "labels": ns(b_axis, seq_axis),
        "features": ns(b_axis, seq_axis, None),
        "vision_embeds": ns(b_axis, None, None),
    }
    return out


def cache_shardings(cfg: ModelConfig, par: ParallelConfig, mesh: Mesh,
                    cache: Params) -> Params:
    """KV caches: batch over data axes; sequence (T) over "model" when the
    batch cannot cover the mesh (decode_32k/long_500k flash-decode style);
    recurrent states: width/heads over "model"."""
    dp = batch_axes(mesh)
    dp_spec = dp if len(dp) > 1 else dp[0]
    dsize = int(np.prod([mesh.shape[a] for a in dp]))
    msize = mesh.shape["model"]

    def leaf_spec(path: str, leaf) -> P:
        shape = leaf.shape
        nd = len(shape)
        if "len" in path:
            return P()
        # scan caches carry a leading period axis: [P, B, ...]; tail caches
        # start at the batch dim.
        off = 1 if "'scan'" in path else 0
        axes = [None] * nd
        if "'k'" in path or "'v'" in path:
            # [B, T, Hkv * D]: the last axis splits on whole KV heads only
            b_i, t_i, w_i = off, off + 1, off + 2
            heads_split = cfg.num_kv_heads % msize == 0
            if shape[b_i] % dsize == 0:
                axes[b_i] = dp_spec
            if shape[t_i] % msize == 0 and not heads_split:
                axes[t_i] = "model"
            elif heads_split:
                axes[w_i] = "model"
            return P(*axes)
        if "'s'" in path:      # rwkv6 state [P, B, H, N, N]
            if shape[off] % dsize == 0:
                axes[off] = dp_spec
            if nd > off + 1 and shape[off + 1] % msize == 0:
                axes[off + 1] = "model"
            return P(*axes)
        if "'h'" in path or "conv" in path or "last_x" in path:
            b_i = off if nd > off else 0
            if nd and shape[b_i] % dsize == 0:
                axes[b_i] = dp_spec
            if shape[nd - 1] % msize == 0:
                axes[nd - 1] = "model"
            return P(*axes)
        return P(*axes)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = [NamedSharding(mesh, leaf_spec(jax.tree_util.keystr(p), l))
           for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, out)
