"""Share of the decode step's device time spent in operations that only
move data (copies, relayouts, slices and updates of the cache; the
`movement` class of `trace.attach_hlo`), in percent: their summed self
time over the `serve_step` program's device time. Read only where at least
99% of that time joined its HLO instruction."""
from benchmarks.chip import trace as T

MODULE = r"serve_step"
MIN_JOINED = 0.99


def read(ctx):
    tr = ctx.trace
    t = tr.module_s(MODULE)
    if t <= 0:
        return None
    joined, _ = T.join_shares(tr, MODULE)
    if joined < MIN_JOINED:
        ctx.log(f"data_movement.decode: {100 * joined!r}% of serve_step's "
                f"device time joined its HLO instruction, under "
                f"{100 * MIN_JOINED}%; not read")
        return None
    return 100.0 * tr.cls_s("movement", MODULE) / t
