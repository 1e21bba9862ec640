"""The attention kernels' share of their roofline: the least time the
chip could spend in the attention the traced steps require
(counts.attention_min_seconds_per_sample: per layer and direction the
larger of flops/peak and bytes/bandwidth) over the device time of the
kernels whose name matches ``pattern``."""
from benchmark import counts


def read(ctx, pattern):
    tr = ctx.trace
    if tr is None or not ctx.traced_steps:
        return None
    spent = tr.seconds_matching(pattern)
    if not spent:
        return None
    least = counts.attention_min_seconds_per_sample(ctx.model, ctx.peaks)
    samples_per_chip = ctx.traced_steps * ctx.samples_per_step / ctx.chips
    return 100.0 * least["seconds"] * samples_per_chip / spent
