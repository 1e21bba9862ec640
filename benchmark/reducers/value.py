"""A number the harness already holds (a counter, a window statistic, a
set-up item), optionally rescaled."""


def read(ctx, key: str, scale: float = 1.0):
    v = ctx.values.get(key)
    return None if v is None else v * scale
