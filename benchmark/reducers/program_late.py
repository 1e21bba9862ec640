"""What the program's late-step recorder says (``dalle_tpu.obs.late``): the
``loop/late_step`` events of the flight ring, one after the ``loop/step``
row of every step that ran over the median of the 32 before it, each with
its ``excess_s``, where the excess was (``where``), what the pulse missed
(``pulse_missed_s``) and the ``cause`` the numbers support.

``what`` selects the reduction over the window's events: ``count``, or the
sum of one numeric attribute; ``cause`` keeps the events of that cause
only. **0.0 where the window's ``loop/step`` rows are there and no step was
late**; left out only where the program has no recorder (the parent of the
PR that added it) or keeps no ring.

The harness's window runs from the ``on_step`` hook of one step to the hook
of a later one, and a ``loop/step`` span from a step's batch fetch to the
end of its ``collab/step``: so of the window's last step only what comes
before the hook is inside the window (in a traced run that hook starts the
profiler, for seconds), and of the step before its first, the hook and
what follows it. A record says on which side of its step's hook the excess
was (``hook_or_after``).
"""
from benchmark.reducers import program_span as S

EVENT = "loop/late_step"


def window_events(ctx, rows):
    """Attributes of the late-step events inside the measured window, or
    None where the ring no longer holds the window's steps."""
    steps = S.window_steps(ctx, rows)
    closed = {S.step_of(r) for r in rows
              if r.get("plane") == S.PLANE and r.get("phase") == S.STEP}
    if not closed.intersection(steps):
        return None
    before_first, last = steps[0] - 1, steps[-1]
    found = []
    for r in rows:
        if r.get("plane") != S.PLANE or r.get("phase") != EVENT:
            continue
        step, attrs = S.step_of(r), r.get("a", {})
        before_hook = not attrs.get("hook_or_after")
        if (step in steps and (step != last or before_hook)) \
                or (step == before_first and not before_hook):
            found.append(attrs)
    return found


def read(ctx, what, cause=None):
    try:
        from dalle_tpu.obs import late  # noqa: F401
    except ImportError:
        return None
    rows = S.ring_rows()
    if not rows:
        return None
    found = window_events(ctx, rows)
    if found is None:
        return None
    if cause is not None:
        found = [a for a in found if a.get("cause") == cause]
    if what == "count":
        return float(len(found))
    return float(sum(a.get(what, 0.0) for a in found))
