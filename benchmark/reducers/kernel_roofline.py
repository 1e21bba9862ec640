"""A kernel family's share of its roofline: the least time the chip could
spend in what the traced steps require of it, over the device time of the
kernels whose name matches ``pattern``. ``least`` names the function of
the configuration's yardstick that gives the least seconds of one sample
(``f(model, peaks) -> {"seconds": ...}``: per call the larger of
flops/peak and bytes/bandwidth; ``yardsticks/dalle.py`` has
``attention_min_seconds_per_sample``). A new kernel's share is one
``layer_metrics/<m>.json`` naming this reducer and one such function; with
``scope`` only the kernels issued under a matching scope path count."""


def read(ctx, pattern, least, scope=None):
    tr = ctx.trace
    if tr is None or not ctx.traced_steps:
        return None
    spent = tr.seconds_matching(pattern, scope)
    if not spent:
        return None
    seconds = getattr(ctx.yardstick, least)(ctx.model, ctx.peaks)["seconds"]
    samples_per_chip = ctx.traced_steps * ctx.samples_per_step / ctx.chips
    return 100.0 * seconds * samples_per_chip / spent
