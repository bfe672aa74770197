"""Model FLOPs of the traced prefill calls over their device time at the
chip's bf16 peak, in percent."""
from benchmarks.chip import work as W


def read(ctx):
    w = ctx.work
    t = ctx.trace.module_s(r"prefill_step")
    if not w.get("prefill_calls") or t <= 0:
        return None
    flops = w["prefill_calls"] * W.prefill_flops(ctx.config, w["batch"],
                                                 w["prompt_len"])
    return 100.0 * flops / (t * ctx.peaks["flops_bf16"])
