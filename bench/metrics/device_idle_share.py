"""Share of the traced training window in which no operation ran on
the device: 100 x (1 - union of device-op intervals / window)."""


def read(ctx):
    busy, window = ctx.trace.busy_s, ctx.trace.window_s
    if busy is None or not window:
        return None
    return 100.0 * (1.0 - busy / window)
