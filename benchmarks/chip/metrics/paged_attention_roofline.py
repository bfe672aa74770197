"""Share of its roofline that the paged decode-attention kernel reaches, in
percent: the least time its required work takes (q and the valid KV prefix
read, the output written; 4·B·Hq·hd·len FLOPs) over the summed device time
of its events."""
from benchmarks.chip import work as W


def read(ctx):
    steps = ctx.work.get("decode_lengths") or []
    layers = W.widths(ctx.config)["layers"]
    pattern = r"^paged_attention(\.\d+)?$"
    n = ctx.trace.op_count(pattern)
    if not steps or n == 0:
        return None
    if n != len(steps) * layers:
        ctx.log(f"paged_attention_roofline: {n} kernel events for "
                f"{len(steps)} steps x {layers} layers; not read")
        return None
    bound, binds = 0.0, set()
    for lens in steps:
        t, b = W.roofline_s(*W.paged_attention(ctx.config, lens), ctx.peaks)
        bound += t * layers
        binds.add(b)
    ctx.log(f"paged_attention_roofline: bound by {'/'.join(sorted(binds))}")
    return 100.0 * bound / ctx.trace.op_s(pattern)
