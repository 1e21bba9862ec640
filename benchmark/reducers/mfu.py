"""Model FLOP/s utilisation: the operations forward and backward require
(benchmark/counts.py; replays not counted) at the measured rate, over
chips x peak."""
from benchmark import counts


def read(ctx):
    rate = ctx.values.get("train_tokens_per_s")   # per chip
    if rate is None:
        return None
    flops_per_token = (counts.train_flops_per_sample(ctx.model)
                       / counts.tokens_per_sample(ctx.model))
    return 100.0 * flops_per_token * rate / ctx.peaks["bf16_flops_per_s"]
