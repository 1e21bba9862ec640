"""Device seconds of the operations whose name matches ``pattern`` as a
share of the device's busy time (``of="busy"``) or of the traced window
(``of="window"``); with ``exposed`` only the part during which no other
operation ran on that device. With ``scope``, only the operations whose
scope path (the program's ``jax.named_scope`` and module names, as
``benchmark/trace.py`` keeps them) matches that expression too: so a
layer's share of the generic ``fusion`` time is one
``layer_metrics/<m>.json`` naming this reducer, and no code."""


def read(ctx, pattern, of="busy", exposed=False, scope=None):
    tr = ctx.trace
    if tr is None:
        return None
    base = tr.busy_s if of == "busy" else tr.window_s
    if not base:
        return None
    secs = tr.exposed_seconds(pattern, scope) if exposed \
        else tr.seconds_matching(pattern, scope)
    return 100.0 * secs / base
