"""The names the step programs give their parts with `jax.named_scope`, and
how to find them again in a compiled program.

A scope sets the `op_name` metadata of the instructions traced under it and
nothing else. The path reads from the outside in, for example in
`serve_step`:

    jit(serve_step)/embed/gather
    jit(serve_step)/layers/while/body/squeeze       the scan slicing its xs
    jit(serve_step)/layers/while/body/dynamic_update_slice   stacking its ys
    jit(serve_step)/layers/while/body/closed_call/attn/qkv/dot_general
    .../attn/kv_cache_write/scatter                 the new K/V into the cache
    .../attn/paged_attention/jit(paged_attention)/kv_relayout/reshape
    .../attn/paged_attention/jit(paged_attention)/pallas_call
    .../attn/out_proj/dot_general
    jit(serve_step)/layers/while/body/closed_call/mlp/dot_general
    jit(serve_step)/head/dot_general

`embed`, `layers` and `head` are outermost. In each layer the mixer half
(norm to residual add) is named for its block kind (`MIXER`) and the FFN
half is `mlp` or `moe`. Inside attention are `qkv`, `kv_cache_write` and
`out_proj`, and the kernels' wrappers (`repro.kernels.ops`) go under the
kernel's name. The scan's own slicing and stacking carry the scope of its
call site and none of their own, so `LAYER_SCAN` picks them out as a
primitive's name directly under the loop body.

A device trace joined to the compiled instructions reads each op's time by
these paths (`benchmarks/chip/scopes.py`).
"""
from __future__ import annotations

import re
from typing import Dict, NamedTuple, Tuple

EMBED, LAYERS, HEAD = "embed", "layers", "head"
ATTN, RGLRU, RWKV6 = "attn", "rglru", "rwkv6"
MLP, MOE = "mlp", "moe"
QKV, KV_CACHE_WRITE, OUT_PROJ = "qkv", "kv_cache_write", "out_proj"
FLASH_ATTENTION, PAGED_ATTENTION = "flash_attention", "paged_attention"
KV_RELAYOUT = "kv_relayout"

# the mixer half's scope for each block kind (`repro.models.lm.BLOCK_*`)
MIXER = {"full": ATTN, "local": ATTN, "rglru": RGLRU, "rwkv6": RWKV6}

LAYER_SCAN = rf"/{LAYERS}/while/body/(?!closed_call$)[a-z_]+$"


def outermost(*names: str) -> str:
    """A pattern for an `op_name` directly inside one of the program's
    outermost scopes."""
    return r"^[^/]+/(" + "|".join(names) + ")/"


def under(*names: str) -> str:
    """A pattern for an `op_name` inside one of the scopes, at any depth."""
    return "/(" + "|".join(names) + ")/"


# ------------------------------------------------- compiled program's text
class Instruction(NamedTuple):
    computation: str
    opcode: str
    shape: str                 # without layouts, e.g. "bf16[32,1024,256]"
    op_name: str               # "" where the instruction has none
    operands: Tuple[str, ...]
    calls: Tuple[str, ...]     # computations named by calls=/body=/condition=


_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition)=%?([\w.\-]+)")


def _split_shape(rest: str) -> Tuple[str, str]:
    """`<shape> <opcode>(...)` -> (shape, the rest); a tuple's shape is in
    parentheses and may hold spaces."""
    end = rest.find(" ")
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    return rest[:end], rest[end:].lstrip()


def instructions(text: str) -> Tuple[Dict[str, Instruction], str]:
    """One module's HLO text (`Compiled.as_text()`) -> its instructions by
    name, and the name of its entry computation."""
    out: Dict[str, Instruction] = {}
    comp, entry = None, ""
    for line in text.splitlines():
        if comp is None:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(2)
                entry = comp if m.group(1) else entry
        elif line.strip() == "}":
            comp = None
        else:
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            shape, rest = _split_shape(m.group(2))
            opcode, _, args = rest.partition("(")
            op_name = _OP_NAME.search(rest)
            out[m.group(1)] = Instruction(
                comp, opcode, re.sub(r"\{[^}]*\}", "", shape),
                op_name.group(1) if op_name else "",
                tuple(re.findall(r"%([\w.\-]+)", args.split(")", 1)[0])),
                tuple(_CALLS.findall(rest)))
    return out, entry
