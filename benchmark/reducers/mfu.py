"""Model FLOP/s utilisation: the operations forward and backward require
(the configuration's yardstick, ``train_flops_per_sample``; replays not
counted) at the measured rate, over chips x peak."""


def read(ctx):
    rate = ctx.values.get("train_tokens_per_s")   # per chip
    if rate is None:
        return None
    flops_per_token = (ctx.yardstick.train_flops_per_sample(ctx.model)
                       / ctx.yardstick.tokens_per_sample(ctx.model))
    return 100.0 * flops_per_token * rate / ctx.peaks["bf16_flops_per_s"]
