"""Share of the traced window in which no op ran on the device, in percent."""


def read(ctx):
    r = ctx.reduction
    if r.window_ns == 0:
        return None
    return 100.0 * (1.0 - r.busy_ns / r.window_ns)
