"""Model FLOPs of the traced decode steps, attention over each valid
prefix included, over their device time at the chip's bf16 peak, in
percent."""
from benchmarks.chip import work as W


def read(ctx):
    steps = ctx.work.get("decode_lengths") or []
    t = ctx.trace.module_s(r"serve_step")
    if not steps or t <= 0:
        return None
    flops = sum(W.decode_flops(ctx.config, lens) for lens in steps)
    return 100.0 * flops / (t * ctx.peaks["flops_bf16"])
