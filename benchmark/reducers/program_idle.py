"""``idle_named_pct``: the share of the traced window's device idle time
that falls inside a leaf span of the program (a span no other span names
as its parent): the part of the idle time that the program's own record
can put a name to.

The program's rows are stamped with ``time.perf_counter()``, the trace
with the profiler's clock. The two are joined by one constant offset,
fitted on the harness's ``bench/collab_step`` host spans: each encloses
one ``collab/step`` row of the ring (the harness wraps the call the row
times), the traced steps being the loop's last. The join checks itself:
every mapped ``collab/step`` row has to lie inside its ``bench/collab_step``
span with no more than TOLERANCE_NS to spare at either end, else the clocks
are not joined and the metric is left out.
"""
from benchmark import trace as T
from benchmark.reducers import program_span

ANCHOR_SPAN = "bench/collab_step"
ANCHOR_PHASE = "collab/step"
TOLERANCE_NS = 50_000
ROUNDING_NS = 2_000      # rows are rounded to the microsecond


def to_ns(seconds):
    return int(round(seconds * 1e9))


def fit_offset(anchors, rows):
    """The offset (ns) that puts each of the last ``len(anchors)`` rows
    (t0, t1 in seconds) inside its anchor (start, end in ns), or None
    where no single offset does so within the tolerance."""
    if not anchors or len(rows) < len(anchors):
        return None
    rows = rows[-len(anchors):]
    lo = max(a0 - to_ns(t0) for (a0, _), (t0, _) in zip(anchors, rows))
    hi = min(a1 - to_ns(t1) for (_, a1), (_, t1) in zip(anchors, rows))
    offset = (lo + hi) // 2
    for (a0, a1), (t0, t1) in zip(anchors, rows):
        head, tail = to_ns(t0) + offset - a0, a1 - to_ns(t1) - offset
        if not (-ROUNDING_NS <= head <= TOLERANCE_NS
                and -ROUNDING_NS <= tail <= TOLERANCE_NS):
            return None
    return offset


def leaf_intervals(rows, offset, window):
    """Merged trace-clock intervals, clipped to the window, of the ring's
    leaf spans (all planes): spans that no span inside them names as its
    parent."""
    lo, hi = window
    found = []
    for r in rows:
        if r.get("dur_s", 0) > 0:
            s = to_ns(r["t0"]) + offset
            e = to_ns(r["t0"] + r["dur_s"]) + offset
            if s < hi and e > lo:
                found.append((r["phase"], r.get("parent"), s, e))
    return T.union(
        (max(s, lo), min(e, hi)) for phase, _, s, e in found
        if not any(parent == phase and s - ROUNDING_NS <= c0
                   and c1 <= e + ROUNDING_NS for _, parent, c0, c1 in found))


def read(ctx):
    tr = ctx.trace
    rows = program_span.ring_rows()
    if tr is None or not rows:
        return None
    anchors = [(s, s + d) for name, s, d in tr.spans if name == ANCHOR_SPAN]
    steps = sorted((r["t0"], r["t0"] + r["dur_s"]) for r in rows
                   if r.get("phase") == ANCHOR_PHASE)
    offset = fit_offset(anchors, steps)
    if offset is None:
        return None
    named = leaf_intervals(rows, offset, tr.window)
    idle = inside = 0
    for dev in tr.devices:
        gaps = T.subtract([tr.window], tr.busy(dev))
        idle += T.total(gaps)
        inside += T.total(gaps) - T.total(T.subtract(gaps, named))
    return 100.0 * inside / idle if idle else None
