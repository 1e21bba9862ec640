"""Median over the window's steps of the summed host-clock seconds of the
named harness spans."""
import statistics


def per_step(ctx, spans):
    rows = [ctx.spans.get(s, []) for s in spans]
    n = min((len(r) for r in rows), default=0)
    return [sum(r[i] for r in rows) for i in range(n)]


def read(ctx, spans):
    steps = per_step(ctx, spans)
    return statistics.median(steps) if steps else None
