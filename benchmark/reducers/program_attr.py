"""An attribute the program puts on its own span rows (``dalle_tpu.obs``,
plane ``train``): over the window's steps, the ``reduce`` (``median``,
``sum`` or ``max``) of attribute ``attr`` of the rows of ``phase``
(``loop/step`` rows carry what the model counts a step, read from the
step's ``aux`` with the loss: an expert layer's load). A load is read by
its median; an event that most steps do not have (a call that took the
slow lowering, a row left uncomputed) by its sum or its maximum, since its
median is 0 whatever happened. Where the program keeps no ring, or its
rows carry no such attribute (another model, the parent of the PR that
added it), there is nothing to read and the metric is left out."""
import statistics

from benchmark.reducers import program_span as S

REDUCE = {"median": statistics.median, "sum": sum, "max": max}


def read(ctx, phase, attr, reduce="median"):
    rows = S.ring_rows()
    if not rows:
        return None
    steps = set(S.window_steps(ctx, rows))
    found = [float(r["a"][attr]) for r in rows
             if r.get("plane") == S.PLANE and r.get("phase") == phase
             and S.step_of(r) in steps
             and isinstance(r.get("a", {}).get(attr), (int, float))]
    return REDUCE[reduce](found) if found else None
