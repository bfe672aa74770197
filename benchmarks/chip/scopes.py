"""The step programs' scopes as the metric readers see them, and the checks
those readers share.

A traced run joins each device op to its compiled instruction
(`trace.attach_hlo`), whose `op_name` is the `jax.named_scope` path the
program set. The names and their nesting are the program's own
(`repro.scopes`). A program that defines none has nothing to read here:
every pattern below then matches nothing, and the readers return None.

The first reader that finds the scopes logs the split of `serve_step` and
`prefill_step` by scope (`log_split`), whichever metric it reads.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from benchmarks.chip import trace as T

try:
    from repro import scopes as P
except ImportError:     # a program that names no scope
    P = None

DECODE = r"serve_step"
PREFILL = r"prefill_step"
MIN_JOINED = 0.99
NOTHING = r"(?!)"

if P is None:
    LAYERS = LAYER_SCAN = KV_CACHE = MLP = NOTHING
    SPLIT = ()
else:
    LAYERS = P.outermost(P.LAYERS)
    LAYER_SCAN = P.LAYER_SCAN
    KV_CACHE = P.under(P.KV_CACHE_WRITE, P.KV_RELAYOUT)
    MLP = P.under(P.MLP)
    # An op counts under the first part whose pattern its scope matches;
    # the inner scopes come before those that hold them.
    SPLIT = (
        (P.EMBED, P.outermost(P.EMBED)),
        (P.HEAD, P.outermost(P.HEAD)),
        *((n, P.under(n)) for n in (
            P.KV_RELAYOUT, P.PAGED_ATTENTION, P.FLASH_ATTENTION,
            P.KV_CACHE_WRITE, P.QKV, P.OUT_PROJ, P.ATTN, P.RGLRU, P.RWKV6,
            P.MLP, P.MOE)),
        ("layer_scan", LAYER_SCAN),
        (P.LAYERS, LAYERS),
    )


def program_s(ctx, metric: str, module: str = DECODE) -> Optional[float]:
    """The program's device time where its scopes can be read, else None:
    it ran no op in the window, under `MIN_JOINED` of its time joined its
    instruction, or no op lies under `layers` (a program that names no
    layer). The last two are logged. The first call for a context logs
    the split of its trace."""
    tr = ctx.trace
    t = tr.module_s(module)
    if t <= 0:
        return None
    joined, _ = T.join_shares(tr, module)
    if joined < MIN_JOINED:
        ctx.log(f"{metric}: {100 * joined!r}% of {module}'s device time "
                f"joined its HLO instruction, under {100 * MIN_JOINED}%; "
                f"not read")
        return None
    if tr.scope_s(LAYERS, module) <= 0:
        ctx.log(f"{metric}: no op of {module} lies under a `layers` scope; "
                f"not read")
        return None
    if not getattr(ctx, "scopes_logged", False):
        ctx.scopes_logged = True
        for m in (DECODE, PREFILL):
            log_split(ctx, m)
    return t


def split(tr: T.Trace, module: str) -> Dict[str, float]:
    """Seconds of the program's self time in each part of `SPLIT`, under
    an `op_name` in none of them ("none"), and under no `op_name` at all
    ("no_op_name": instructions that the compiler made, which no scope
    reaches, or ops not joined)."""
    out = {name: 0.0 for name, _ in SPLIT}
    out["none"] = out["no_op_name"] = 0.0
    for o in tr.select(module=module):
        name = next((n for n, pat in SPLIT if re.search(pat, o.scope)),
                    "none" if o.scope else "no_op_name")
        out[name] += o.self_ns * 1e-9
    return out


def log_split(ctx, module: str) -> None:
    """Logs `split`, the share of the program's time under `embed`,
    `layers` or `head`, and, as a diagnostic, that share of the time whose
    instruction has an `op_name`."""
    parts = split(ctx.trace, module)
    total = sum(parts.values())
    named = total - parts["no_op_name"]
    if named <= 0:
        return
    inside = named - parts["none"]
    ctx.log(f"scopes {module}: " + ", ".join(
        f"{k} {v!r} s" for k, v in parts.items())
        + f"; under embed, layers or head {100 * inside / total!r}% of "
        f"the time ({100 * inside / named!r}% of the time with an op_name)")
