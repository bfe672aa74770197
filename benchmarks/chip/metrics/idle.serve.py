"""Share of the traced serving window in which no operation ran on the
device, in percent."""


def read(ctx):
    win = ctx.trace.window_s()
    if win <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / win)
