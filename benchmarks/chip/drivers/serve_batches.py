"""Closed loop of static serving batches, sent back to back.

Each batch is `batch` prompts of `prompt_len` token ids drawn from the seed.
The driver prefills them and decodes `new_tokens` greedy tokens through the
program's own compiled programs, built as `launch/serve.serve()` builds them:
`runtime/steps.make_prefill_step` and `make_serve_step` under
`jax.jit(...).lower(...).compile()`, `ParallelConfig()`, no donation, with
the traffic's `use_kernels`. The next token is the argmax, taken on the
device. The host waits for a batch's first token and its last, and stays
at most `MAX_AHEAD` decode steps ahead of the device: steps are dispatched
ahead, and no more caches are alive than that (nothing donates a cache,
so each step in flight holds its own).

The program's parameters are built from the reference's weights by the
configuration file's `program_params` table (program path -> weight name),
so a configuration of another family brings its table and its reference,
and this driver stays as it is.

A request's time to first token runs from the start of its batch to the
first token on the host; its time per output token is (last − first) /
(tokens − 1). The window runs whole batches until `seconds` have passed.
"""
from __future__ import annotations

import collections
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

SPANS = ("make_batch", "prefill", "sample", "decode", "wait_first",
         "wait_ahead", "wait_last")
MAX_AHEAD = 4          # decode steps the host dispatches ahead of the device
CHECK_REQUESTS = 4     # finished requests the reference check compares
TRACE_BATCHES = 1      # batches a traced run records


@dataclass
class Batch:
    prompts: np.ndarray            # [B, S] host
    tokens: Any                    # [B, n] device, served tokens
    t_start: float
    t_first: float
    t_last: float
    lengths: List[List[int]] = field(default_factory=list)


@dataclass
class State:
    cfg_json: dict
    traffic: dict
    seed: int
    reference: Any
    program_cfg: Any = None
    devices: Any = None
    params: Any = None
    prefill: Any = None
    decode: Any = None
    sample: Any = None
    new_cache: Any = None
    phases: Dict[str, float] = field(default_factory=dict)
    batches: List[Batch] = field(default_factory=list)
    next_batch: int = 0


def program_params(table: dict, w: dict) -> Any:
    """The program's parameter tree from the reference's weights: `table`
    maps each parameter's path (keys joined by ".", a number for a tuple's
    index) to the name of the weight it takes."""
    root: dict = {}
    for path, name in table.items():
        *parents, leaf = path.split(".")
        node = root
        for key in parents:
            node = node.setdefault(key, {})
        node[leaf] = w[name]

    def build(node):
        if not isinstance(node, dict):
            return node
        if all(k.isdigit() for k in node):
            return tuple(build(node[str(i)]) for i in range(len(node)))
        return {k: build(v) for k, v in node.items()}

    return build(root)


def _params(state: State, seed: int) -> Any:
    return program_params(state.cfg_json["program_params"],
                          state.reference.init_weights(state.cfg_json, seed))


def _prompts(state: State, stream: tuple) -> np.ndarray:
    t = state.traffic
    rng = np.random.default_rng([state.seed, *stream])
    return rng.integers(0, state.cfg_json["vocab_size"],
                        (t["batch"], t["prompt_len"]), dtype=np.int32)


def setup(state: State) -> State:
    from repro import configs
    from repro.models import lm
    from repro.runtime import steps

    t = state.traffic
    cfg = state.program_cfg
    B, S, n = t["batch"], t["prompt_len"], t["new_tokens"]
    if S + n - 1 > t["cache_len"]:
        raise ValueError("cache_len is shorter than prompt + new tokens")

    t0 = time.perf_counter()
    params = _params(state, state.seed)
    want = jax.eval_shape(lambda k: lm.init_model(cfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise ValueError("weights do not match the program's parameters")
    jax.block_until_ready(params)
    state.phases["weights"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    par = configs.ParallelConfig()
    kernels = bool(t["use_kernels"])
    state.new_cache = jax.jit(lambda: lm.init_cache(cfg, B, t["cache_len"]))
    cache = state.new_cache()
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    state.prefill = jax.jit(steps.make_prefill_step(cfg, par, kernels)).lower(
        params, batch, cache).compile()
    state.decode = jax.jit(steps.make_serve_step(cfg, par, kernels)).lower(
        params, tok, cache).compile()

    def greedy(logits, buf, i):
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        return nxt, jax.lax.dynamic_update_slice(buf, nxt, (0, i))

    logit_spec = jax.ShapeDtypeStruct((B, 1, cfg.vocab_size), jnp.bfloat16)
    state.sample = jax.jit(greedy).lower(
        logit_spec, jax.ShapeDtypeStruct((B, n), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    state.params = params
    state.phases["compile"] = time.perf_counter() - t0

    # warm-up: every program once (prefill, two decode steps, sampling)
    t0 = time.perf_counter()
    run_batch(state, _prompts(state, (1,)), keep=False, steps=3)
    state.phases["warm_up"] = time.perf_counter() - t0
    return state


def run_batch(state: State, prompts: np.ndarray, keep: bool = True,
              steps: int = 0) -> Batch:
    """Prefill and decode one batch; `steps` cuts it short (warm-up)."""
    n = state.traffic["new_tokens"]
    t_start = time.perf_counter()
    with TraceAnnotation("make_batch"):
        toks = jax.device_put(prompts)
        cache = state.new_cache()
        buf = jnp.zeros((prompts.shape[0], n), jnp.int32)
    with TraceAnnotation("prefill"):
        logits, cache = state.prefill(state.params, {"tokens": toks}, cache)
    with TraceAnnotation("sample"):
        tok, buf = state.sample(logits, buf, np.int32(0))
    with TraceAnnotation("wait_first"):
        tok.block_until_ready()
    t_first = time.perf_counter()
    S = prompts.shape[1]
    lengths = []
    in_flight = collections.deque()
    for i in range(1, steps or n):
        with TraceAnnotation("decode"):
            logits, cache = state.decode(state.params, tok, cache)
        with TraceAnnotation("sample"):
            tok, buf = state.sample(logits, buf, np.int32(i))
        lengths.append([S + i] * prompts.shape[0])
        in_flight.append(tok)
        if len(in_flight) > MAX_AHEAD:
            with TraceAnnotation("wait_ahead"):
                in_flight.popleft().block_until_ready()
    with TraceAnnotation("wait_last"):
        buf.block_until_ready()
    t_last = time.perf_counter()
    b = Batch(prompts, buf, t_start, t_first, t_last, lengths)
    if keep:
        state.batches.append(b)
    return b


def window(state: State, seconds: float, traced: bool) -> Dict[str, Any]:
    """Whole batches until `seconds` have passed (`TRACE_BATCHES` batches
    when traced). Returns what the metrics read."""
    t0 = time.perf_counter()
    limit = TRACE_BATCHES if traced else None
    while True:
        run_batch(state, _prompts(state, (0, state.next_batch)))
        state.next_batch += 1
        if limit is not None and state.next_batch >= limit:
            break
        if limit is None and time.perf_counter() - t0 >= seconds:
            break
    return {"t0": t0, "t1": time.perf_counter()}


def end_to_end(state: State, win: Dict[str, Any]) -> Dict[str, float]:
    n = state.traffic["new_tokens"]
    ttft, tpot, served = [], [], 0
    for b in state.batches:
        B = b.prompts.shape[0]
        ttft += [(b.t_first - b.t_start) * 1e3] * B
        tpot += [(b.t_last - b.t_first) * 1e3 / (n - 1)] * B
        served += B * n
    span = win["t1"] - win["t0"]
    return {"serve_tok_s": served / span,
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "tpot_p95_ms": float(np.percentile(tpot, 95))}


def describe(state: State, win: Dict[str, Any]) -> str:
    n = state.traffic["new_tokens"]
    return "batches (ttft ms, tpot ms): " + ", ".join(
        f"({(b.t_first - b.t_start) * 1e3:.2f}, "
        f"{(b.t_last - b.t_first) * 1e3 / (n - 1):.3f})"
        for b in state.batches)


def counts(state: State) -> Dict[str, int]:
    reqs = sum(b.prompts.shape[0] for b in state.batches)
    return {"attempted": reqs, "failed": 0}


def programs(state: State) -> Dict[str, Any]:
    """The compiled programs the window drives, for the trace reduction."""
    return {"prefill_step": state.prefill, "serve_step": state.decode,
            "sample": state.sample}


def work(state: State) -> Dict[str, Any]:
    """Shapes of every call the window made, for the metric readers."""
    t = state.traffic
    return {"kind": "serve", "batch": t["batch"], "prompt_len": t["prompt_len"],
            "prefill_calls": len(state.batches),
            "decode_lengths": [lens for b in state.batches
                               for lens in b.lengths]}


def _sample(state: State):
    """The requests the check compares: `CHECK_REQUESTS` of those the
    window finished, drawn from the seed (all have the same length)."""
    done = [(b, r) for b in state.batches for r in range(b.prompts.shape[0])]
    rng = np.random.default_rng([state.seed, 2])
    pick = rng.choice(len(done), size=min(CHECK_REQUESTS, len(done)), replace=False)
    prompts = np.stack([done[i][0].prompts[done[i][1]] for i in pick])
    served = np.stack([np.asarray(done[i][0].tokens)[done[i][1]]
                       for i in pick])
    return prompts, served


def check(state: State, limits: dict) -> Dict[str, tuple]:
    """Frees the program's state, then runs the reference over a sample of
    the served requests: the widest gap by which a served token's logit
    lies below the reference's best at its position."""
    prompts, served = _sample(state)
    free(state)
    w = state.reference.init_weights(state.cfg_json, state.seed)
    gaps, _ = state.reference.served_gaps(state.cfg_json, w, prompts, served)
    del w
    print(f"reference check: {served.shape[0]} requests, {served.size} "
          f"served tokens", flush=True)
    return {"logit_gap": (float(gaps.max()), limits["logit_gap"])}


def calibrate(state: State, seeds, controls: bool = True
              ) -> List[Dict[str, Any]]:
    """Readings for the limit, one batch at the cell's load per seed: the
    program's widest gap and the fp8 control's, on the same requests."""
    out = []
    for seed in seeds:
        state.seed, state.batches = seed, []
        state.params = _params(state, seed)
        run_batch(state, _prompts(state, (0, 0)))
        prompts, served = _sample(state)
        state.params, state.batches = None, []
        w = state.reference.init_weights(state.cfg_json, seed)
        gaps, ctrl = state.reference.served_gaps(state.cfg_json, w, prompts,
                                                 served, control=controls)
        del w
        row = {"seed": seed, "logit_gap": float(gaps.max()),
               "tokens": int(served.size)}
        if controls:
            row["control_logit_gap"] = float(ctrl.max())
        out.append(row)
        print(json.dumps(out[-1]), flush=True)
    return out


def free(state: State) -> None:
    for b in state.batches:
        b.tokens = None
    state.params = state.prefill = state.decode = state.sample = None
    state.new_cache = None
