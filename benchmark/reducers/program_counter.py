"""What the program's compile counter says (``dalle_tpu.obs.compiles``:
JAX's tracing, lowering and backend compile-or-load seconds, kept per
program and per span open on the thread that compiled).

- ``span=<phase>``: the summed seconds of ``kinds`` spent while that span
  was open;
- ``program=<fun_name>``: the same for one jitted program, wherever its
  first call fell;
- ``after_first_step``: how many backend compiles came after the loop's
  first step had closed (the inside twin of ``window_compiles``).

Where the program has no counter there is nothing to read and the metric
is left out.
"""


def snapshot():
    try:
        from dalle_tpu.obs import compiles
    except ImportError:
        return None
    counter = compiles.installed()
    return counter.snapshot() if counter is not None else None


def read(ctx, span=None, program=None, after_first_step=False,
         kinds=("trace", "lower", "compile")):
    snap = snapshot()
    if snap is None:
        return None
    if after_first_step:
        return float(len(snap["after_first_step"]))
    row = (snap["by_span"].get(span) if span is not None
           else snap["by_program"].get(program))
    if row is None:
        return None
    return sum(row[k + "_s"] for k in kinds)
