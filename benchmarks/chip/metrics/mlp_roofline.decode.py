"""Share of its roofline that the decode step's MLP reaches, in percent:
the least time its required work takes (the SwiGLU weights, gate, up and
down, `layers × 3 × d × ff` bf16 values read once per step, and
`2 × B × layers × 3 × d × ff` FLOPs) over the self time of the ops under
the `mlp` scope of `serve_step`. Read only where at least 99% of the
program's time joined its HLO instruction and it names its layers."""
from benchmarks.chip import scopes as S
from benchmarks.chip import work as W


def read(ctx):
    steps = ctx.work.get("decode_lengths") or []
    if not steps or S.program_s(ctx, "mlp_roofline.decode") is None:
        return None
    t = ctx.trace.scope_s(S.MLP, S.DECODE)
    if t <= 0:
        ctx.log("mlp_roofline.decode: no op of serve_step lies under an "
                "`mlp` scope; not read")
        return None
    w = W.widths(ctx.config)
    params = w["layers"] * 3 * w["d"] * w["ff"]
    bound, binds = 0.0, set()
    for lens in steps:
        s, b = W.roofline_s(2 * len(lens) * params, params * W.BF16,
                            ctx.peaks)
        bound += s
        binds.add(b)
    ctx.log(f"mlp_roofline.decode: bound by {'/'.join(sorted(binds))}")
    return 100.0 * bound / t
