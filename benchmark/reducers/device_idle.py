"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
