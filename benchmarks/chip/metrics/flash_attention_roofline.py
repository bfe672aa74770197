"""Share of its roofline that the causal flash-attention kernel reaches in
prefill, in percent: the least time its required work takes (half the
square of QK^T and PV; q, k, v read once and o written once) over the
summed device time of its events."""
from benchmarks.chip import work as W


def read(ctx):
    w = ctx.work
    calls = w.get("prefill_calls") or 0
    layers = W.widths(ctx.config)["layers"]
    pattern = r"^flash_attention(\.\d+)?$"
    n = ctx.trace.op_count(pattern)
    if not calls or n == 0:
        return None
    if n != calls * layers:
        ctx.log(f"flash_attention_roofline: {n} kernel events for {calls} "
                f"prefill calls x {layers} layers; not read")
        return None
    t, b = W.roofline_s(*W.flash_attention(ctx.config, w["batch"],
                                           w["prompt_len"]), ctx.peaks)
    ctx.log(f"flash_attention_roofline: bound by {b}")
    return 100.0 * t * calls * layers / ctx.trace.op_s(pattern)
