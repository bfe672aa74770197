"""Share of the decode step's device time spent in the layer scan's own
ops, in percent: its slicing of each layer's weights and cache out of the
stacked `xs` and its stacking of each layer's updated cache into the
`ys`, the ops whose scope is a primitive's name directly under
`layers/while/body`, over the `serve_step` program's device time. Read
only where at least 99% of that time joined its HLO instruction and the
program names its layers."""
from benchmarks.chip import scopes as S


def read(ctx):
    t = S.program_s(ctx, "layer_scan.decode")
    if t is None:
        return None
    return 100.0 * ctx.trace.scope_s(S.LAYER_SCAN, S.DECODE) / t
