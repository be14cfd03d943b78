"""Share of the traced window in which no operation ran on the device:
1 - (union of the `XLA Ops` intervals) / window, in percent."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.busy_ns or tr.window_s <= 0:
        return None
    return (1.0 - tr.busy_s / tr.window_s) * 100.0
