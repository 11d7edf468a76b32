"""Share of the traced window in which no op ran on the device:
1 - (union of device op intervals) / window, averaged over the chips."""


def read(ctx):
    r = ctx.reduction
    if r is None or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_s() / r.window_s)
