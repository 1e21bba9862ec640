"""Share of the median step interval that is not the named spans (the
grad step's dispatch and wait): what the host loop adds around the jitted
step."""
from benchmark.reducers import span_median


def read(ctx, spans):
    inside = span_median.read(ctx, spans)
    interval = ctx.values.get("step_interval_s")
    if inside is None or not interval:
        return None
    return 100.0 * (1.0 - inside / interval)
