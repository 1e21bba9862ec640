"""What the program's own spans say (``dalle_tpu.obs``, plane ``train``):
rows of the trainer's flight ring, which is always on, read after the run.

``how`` selects the reduction over the rows of the named ``phases``:

- ``open_seconds``: seconds during which any of them was open (set-up
  spans; they may nest or repeat, an interval counts once);
- ``step_median``: per step the summed seconds of the phases, median over
  the window's steps;
- ``self_pct``: per step the part of the (one) phase's span that no child
  row covers, as a share of the span: median self over median span.

The window's steps are counted back from the loop's last step: the traced
steps come last, the window's ``n_intervals`` steps before them. A row
says which step it belongs to (``trace``: ``step:<n>``) and which span
caused it (``parent``). Where the program keeps no ring, or the rows were
evicted, there is nothing to read and the metric is left out.
"""
import statistics

from benchmark import trace as T

PLANE = "train"
STEP = "loop/step"


def ring_rows():
    """The process-default tracer's rows, or None where the program has
    none (the parent of the PR that added it configures no tracer)."""
    try:
        from dalle_tpu.obs.trace import default_tracer
    except ImportError:
        return None
    tracer = default_tracer()
    return tracer.dump() if tracer is not None else None


def step_of(row):
    kind, _, n = str(row.get("trace", "")).partition(":")
    return int(n) if kind == "step" and n.isdigit() else None


def spans(rows, phases=None):
    """Span rows (events left out) of plane ``train``, as
    (step, phase, parent, t0, t1)."""
    return [(step_of(r), r["phase"], r.get("parent"), r["t0"],
             r["t0"] + r["dur_s"]) for r in rows
            if r.get("plane") == PLANE and r.get("dur_s", 0) > 0
            and (phases is None or r["phase"] in phases)]


def covered(intervals):
    """Seconds the union of (t0, t1) intervals covers."""
    return T.total(T.union(intervals))


def window_steps(ctx, rows):
    """Step numbers of the measured window: the ``n_intervals`` steps
    before the traced ones, which are the loop's last."""
    last = max((step_of(r) or 0 for r in rows if r.get("phase") == STEP),
               default=0)
    n = ctx.values.get("n_intervals")
    if not last or not n:
        return range(0)
    hi = last - (ctx.traced_steps or 0)
    return range(max(hi - n, 0) + 1, hi + 1)


def read(ctx, phases, how):
    rows = ring_rows()
    if not rows:
        return None
    if how == "open_seconds":
        found = [(t0, t1) for _, _, _, t0, t1 in spans(rows, phases)]
        return covered(found) if found else None
    steps = set(window_steps(ctx, rows))
    if how == "step_median":
        per_step = {}
        for step, _, _, t0, t1 in spans(rows, phases):
            if step in steps:
                per_step[step] = per_step.get(step, 0.0) + (t1 - t0)
        return statistics.median(per_step.values()) if per_step else None
    if how == "self_pct":
        (phase,) = phases
        whole, children = {}, {}
        for step, name, parent, t0, t1 in spans(rows):
            if step not in steps:
                continue
            if name == phase:
                whole[step] = (t0, t1)
            elif parent == phase:
                children.setdefault(step, []).append((t0, t1))
        if not whole:
            return None
        selfs = [t1 - t0 - covered(children.get(step, []))
                 for step, (t0, t1) in whole.items()]
        spans_s = [t1 - t0 for t0, t1 in whole.values()]
        return 100.0 * statistics.median(selfs) / statistics.median(spans_s)
    raise ValueError(f"program_span: unknown reduction {how!r}")
