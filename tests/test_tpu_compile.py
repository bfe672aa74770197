"""Compile the main-path Pallas kernels at real widths for a described TPU
v5e, without the chip: the TPU compiler refuses what interpret mode accepts
(blocks off the (8, 128) tiling, unaligned DMA slices). Nothing runs, so
these tests say nothing about results or speed; tests/test_kernels.py checks
results in interpret mode.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import scopes as P
from repro.kernels.async_gather import async_gather, row_shape
from repro.kernels.async_scatter import async_scatter
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.stream_triad import stream_triad

VOCAB, D_MODEL = 151936, 2048      # qwen2.5-3b embedding table
ROWS = 1024                        # indices per gather / scatter call


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs) -> str:
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


# every decoder config whose decode reaches the kernel, and plain MHA
@pytest.mark.parametrize("hq,hkv,d", [
    (16, 2, 128),     # qwen2.5-3b
    (16, 8, 64),      # granite-moe-1b-a400m
    (4, 4, 128),      # MHA
    (64, 8, 128),     # kimi-k2-1t-a32b
    (40, 8, 128),     # qwen2.5-32b
    (28, 4, 128),     # qwen2-7b
    (24, 8, 128),     # phi4-mini-3.8b
    (12, 2, 128),     # qwen2-vl-2b
])
def test_paged_attention_compiles(one_chip, hq, hkv, d):
    B, T = 8, 1024
    q = jax.ShapeDtypeStruct((B, hq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, T, hkv * d), jnp.bfloat16,
                              sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, n: paged_attention(q, k, v, n, page=512),
             q, kv, kv, lens)


@pytest.mark.parametrize("hq,hkv,d,causal", [(16, 2, 128, True),
                                             (16, 8, 64, True),
                                             (16, 16, 80, False)])
def test_flash_attention_compiles(one_chip, hq, hkv, d, causal):
    B, S = 2, 1024
    q = jax.ShapeDtypeStruct((B, hq, S, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, hkv, S, d), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=causal),
             q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_async_gather_compiles(one_chip, dtype):
    table = jax.ShapeDtypeStruct((VOCAB,) + row_shape(D_MODEL), dtype,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    _compile(lambda t, i: async_gather(t, i), table, idx)


@pytest.mark.parametrize("dtype,op", [(jnp.bfloat16, "add"),
                                      (jnp.float32, "add"),
                                      (jnp.int32, "xor")])
def test_async_scatter_compiles(one_chip, dtype, op):
    tiles = row_shape(D_MODEL)
    table = jax.ShapeDtypeStruct((VOCAB,) + tiles, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    upd = jax.ShapeDtypeStruct((ROWS,) + tiles, dtype, sharding=one_chip)
    _compile(lambda t, i, u: async_scatter(t, i, u, op=op), table, idx, upd)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_stream_triad_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((1 << 24,), dtype, sharding=one_chip)
    _compile(lambda b, c: stream_triad(b, c, 3.0), x, x)


# ----------------------------------------------- the step programs' scopes
# qwen2.5-3b's serve_step (batch 32, cache 1024) and prefill_step (batch
# 32, prompts of 512) at full width, compiled for the described chip as the
# chip benchmark compiles them. The device trace names each op by its
# instruction, and its scope by the instruction's op_name: each kind of
# work below is found by what it computes (opcodes, fused or not, and
# shapes), never by the compiler's instruction names, and has to carry its
# scope (`repro.scopes`).
L, B, T, S = 36, 32, 1024, 512
FF, HQ, HKV, HD = 11008, 16, 2, 128
TABLE = f"bf16[{VOCAB},{D_MODEL}]"
STACKED_CACHE = f"bf16[{L},{B},{T},{HKV * HD}]"
PAGED_CACHE = f"bf16[{B},{T},{HKV * HD}]"      # one layer's cache, as stored
# a layer's cache in either layout: as stored, and split over its KV heads
LAYER_CACHES = {PAGED_CACHE, f"bf16[{B},{T},{HKV},{HD}]"}
RELAYOUT = {"reshape", "copy", "transpose"}
# the stacked attention weights: wq and wo, wk and wv
ATTN_WEIGHTS = {f"bf16[{L},{D_MODEL},{HQ * HD}]",
                f"bf16[{L},{D_MODEL},{HKV * HD}]"}
MLP_WEIGHTS = {f"bf16[{L},{D_MODEL},{FF}]", f"bf16[{L},{FF},{D_MODEL}]"}
MATMUL = {"dot", "convolution"}

# {program: {role: (is one of its instructions, the scope it lies in)}}
_BOTH = {
    # the token gather from the embedding table
    "embed": (lambda i: "gather" in i.ops and TABLE in i.args,
              P.outermost(P.EMBED)),
    # the tied head's matmul over the embedding table
    "head": (lambda i: i.ops & MATMUL and TABLE in i.args,
             P.outermost(P.HEAD)),
    # every matmul: the attention's projections, the MLP and the head
    "matmul": (lambda i: i.ops & MATMUL,
               f"{P.under(P.QKV, P.OUT_PROJ, P.MLP)}|"
               f"{P.outermost(P.HEAD)}"),
    # the SwiGLU's matmuls, which read the stacked MLP weights
    "mlp": (lambda i: i.ops & MATMUL and i.args & MLP_WEIGHTS,
            P.under(P.MLP)),
    # the scan slicing each layer's cache out of the stacked cache,
    # and stacking it back
    "layer_scan": (lambda i: i.ops & {"dynamic-slice",
                                      "dynamic-update-slice"}
                   and STACKED_CACHE in i.args, P.LAYER_SCAN),
}
ROLES = {
    "serve_step": {
        **_BOTH,
        # the new token's K/V scattered into the layer's cache
        "kv_cache_write": (lambda i: "scatter" in i.ops,
                           P.under(P.KV_CACHE_WRITE)),
        "paged_attention": (lambda i: i.opcode == "custom-call"
                            and PAGED_CACHE in i.args,
                            rf"/{P.PAGED_ATTENTION}/.*/pallas_call$"),
    },
    "prefill_step": {
        **_BOTH,
        # the prompt's K/V written into the layer's cache
        "kv_cache_write": (
            lambda i: "dynamic-update-slice" in i.ops
            and f"bf16[1,{B},{S},{HKV * HD}]" in i.args,
            P.under(P.KV_CACHE_WRITE)),
        "flash_attention": (lambda i: i.opcode == "custom-call"
                            and f"bf16[{B},{HQ},{S},{HD}]" in i.args,
                            rf"/{P.FLASH_ATTENTION}/.*/pallas_call$"),
    },
}
CASES = [(program, role) for program, roles in ROLES.items()
         for role in roles]
# The work of the entry computation and of the loop bodies (a matmul, a
# kernel, a fusion or a data movement) that lies under no layer, by its
# op_name below the program's, each with its reason.
OUTSIDE_LAYERS = {
    # cache["len"] + 1 (decode) or + S (prefill), fused with the positions
    # cast for rope, which is hoisted out of the layer loop
    "add",
    # the stacked f32 norm scales cast to bf16 by
    # lm.cast_params_for_compute, before any layer
    "convert_element_type",
}
WORK = {"dot", "convolution", "custom-call", "fusion", "copy", "bitcast",
        "reshape", "transpose", "slice", "dynamic-slice",
        "dynamic-update-slice", "concatenate", "pad", "broadcast", "convert"}


def _top_level(text: str) -> list:
    """The instructions of the entry computation and of every while
    loop's body and condition, each with its name, the opcodes it computes
    (those of its fused computations, for a fusion), its operands' names
    and their shapes."""
    insts, entry = P.instructions(text)
    comps = {}
    for name, inst in insts.items():
        comps.setdefault(inst.computation, []).append(name)

    def opcodes(name):
        inst = insts[name]
        if inst.opcode != "fusion":
            return {inst.opcode}
        return set().union(*(opcodes(n) for c in inst.calls
                             for n in comps.get(c, ())))

    top = {entry} | {c for inst in insts.values() if inst.opcode == "while"
                     for c in inst.calls}
    return [SimpleNamespace(name=name, opcode=inst.opcode, shape=inst.shape,
                            op_name=inst.op_name, ops=opcodes(name),
                            operands=inst.operands,
                            args={insts[o].shape for o in inst.operands
                                  if o in insts})
            for name, inst in insts.items() if inst.computation in top]


@pytest.fixture(scope="module")
def step_programs(one_chip, monkeypatch_module):
    from repro import configs
    from repro.kernels import ops
    from repro.models import lm
    from repro.runtime import steps

    monkeypatch_module.setattr(ops, "_interpret", lambda: False)
    cfg = configs.get_config("qwen2.5-3b")
    par = configs.ParallelConfig()

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda k: lm.init_model(cfg, k, jnp.bfloat16), jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(lambda: lm.init_cache(cfg, B, T)))

    def ids(n):
        return jax.ShapeDtypeStruct((B, n), jnp.int32, sharding=one_chip)

    return {
        "serve_step": _compile(steps.make_serve_step(cfg, par, True),
                               params, ids(1), cache),
        "prefill_step": _compile(steps.make_prefill_step(cfg, par, True),
                                 params, {"tokens": ids(S)}, cache),
    }


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


@pytest.mark.parametrize("program,role", CASES,
                         ids=[f"{p}-{r}" for p, r in CASES])
def test_step_program_ops_carry_their_scopes(step_programs, program, role):
    """The work the chip benchmark's readers select by scope is in the
    compiled program, and all of it carries its scope."""
    found, scope = ROLES[program][role]
    ops = [i for i in _top_level(step_programs[program]) if found(i)]
    assert ops, f"no {role} instruction in {program}"
    assert [i.op_name for i in ops if not re.search(scope, i.op_name)] == []


@pytest.mark.parametrize("program", ["serve_step", "prefill_step"])
def test_step_program_work_lies_under_embed_layers_or_head(step_programs,
                                                           program):
    outside = {i.op_name for i in _top_level(step_programs[program])
               if i.opcode in WORK and i.op_name
               and not re.match(P.outermost(P.EMBED, P.LAYERS, P.HEAD),
                                i.op_name)}
    assert outside == {f"jit({program})/{n}" for n in OUTSIDE_LAYERS}


def _relays(i) -> bool:
    """A copy, reshape or transpose, alone or as all of a fusion."""
    return (i.opcode in RELAYOUT or i.opcode == "fusion"
            and i.ops - {"parameter", "constant", "bitcast"} <= RELAYOUT)


def test_serve_step_reads_the_cache_where_it_is_stored(step_programs):
    """The cache is stored in the layout the paged kernel reads: no
    reshape, copy or transpose (alone or as all of a fusion) takes or makes
    a layer's cache, and the kernel's cache operands are the outputs of the
    `kv_cache_write` scatter."""
    top = {i.name: i for i in _top_level(step_programs["serve_step"])}
    relaid = [i.name for i in top.values()
              if _relays(i) and ({i.shape} | i.args) & LAYER_CACHES]
    assert relaid == []
    kernels = [i for i in top.values()
               if i.opcode == "custom-call" and PAGED_CACHE in i.args]
    assert len(kernels) == 1
    caches = [top[o] for o in kernels[0].operands
              if o in top and top[o].shape == PAGED_CACHE]
    assert len(caches) == 2
    assert all("scatter" in c.ops
               and re.search(P.under(P.KV_CACHE_WRITE), c.op_name)
               for c in caches), [(c.name, c.op_name) for c in caches]


@pytest.mark.parametrize("program", ["serve_step", "prefill_step"])
def test_step_program_reads_attention_weights_where_stored(step_programs,
                                                           program):
    """Each of a layer's wq, wk, wv and wo is read out of the stacked
    parameters by its matmul alone: no copy, transpose or stand-alone
    slice of a layer's attention weight. (Rope written as a split and
    concatenation of each head's halves made `serve_step` slice wq and wk
    out of the stack and transpose them, in every layer at every step.)"""
    top = _top_level(step_programs[program])
    shapes = {i.name: i.shape for i in top}
    readers = {}
    for i in top:
        if i.opcode == "tuple":         # the layer loop's carry
            continue
        for o in i.operands:
            if shapes.get(o) in ATTN_WEIGHTS:
                readers.setdefault(o, []).append(i)
    assert len(readers) == 4
    assert {o: [(i.name, sorted(i.ops)) for i in r]
            for o, r in readers.items()
            if len(r) != 1 or r[0].opcode != "fusion"
            or not r[0].ops & MATMUL} == {}


def test_serve_step_writes_the_cache_rows_qkv_computes(step_programs):
    """The rows the `kv_cache_write` scatters write (the new token's roped
    K, and its V) are the outputs of the `qkv` fusions that compute them,
    and no copy or transpose lies under `qkv`: nothing relays the roped K
    into the cache's row layout."""
    top = {i.name: i for i in _top_level(step_programs["serve_step"])}
    scatters = [i for i in top.values()
                if "scatter" in i.ops and i.shape == PAGED_CACHE]
    assert len(scatters) == 2
    rows = [top[o] for s in scatters for o in s.operands
            if o in top and top[o].shape == f"bf16[{B},{HKV * HD}]"]
    assert len(rows) == 2
    assert [(r.name, r.op_name) for r in rows
            if _relays(r) or not re.search(P.under(P.QKV), r.op_name)
            or not r.ops & {"add", "multiply"}] == []
    assert [i.name for i in top.values()
            if re.search(P.under(P.QKV), i.op_name) and _relays(i)
            and i.ops & {"copy", "transpose"}] == []
