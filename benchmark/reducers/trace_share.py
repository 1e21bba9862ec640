"""Device seconds of the operations whose name matches ``pattern`` as a
share of the device's busy time (``of="busy"``) or of the traced window
(``of="window"``); with ``exposed`` only the part during which no other
operation ran on that device."""


def read(ctx, pattern, of="busy", exposed=False):
    tr = ctx.trace
    if tr is None:
        return None
    base = tr.busy_s if of == "busy" else tr.window_s
    if not base:
        return None
    secs = tr.exposed_seconds(pattern) if exposed \
        else tr.seconds_matching(pattern)
    return 100.0 * secs / base
