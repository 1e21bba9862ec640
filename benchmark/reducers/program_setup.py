"""What the program says of its own set-up, where ``program_counter`` and
``program_span`` cannot read it (``dalle_tpu.obs.compiles`` since PR 54):

- ``what="span_self", span=<phase>``: the compile counter's seconds while
  that span was open with each second counted once: tracing and lowering
  in their self form (an event's seconds less those of the events inside
  it), and the backend's compile-or-load, which holds no other;
- ``what="account"``: the same three of the counter's ``total`` as they
  stood when the loop's first step closed, from the ``setup/account``
  event the program writes into its ring there;
- ``what="again_calls"``: over the Mosaic call sites (the counter's
  ``by_site``), the traced calls of a ``(site, key)`` that had been traced
  in the process before.

Where the program keeps none of these (no counter, a counter with no self
seconds or no ``by_site``, a ring with no such event: the parent of the PR
that added them) there is nothing to read and the metric is left out.
"""
from benchmark.reducers import program_counter, program_span

KINDS = ("trace_self_s", "lower_self_s", "compile_s")
ACCOUNT = "setup/account"


def each_second_once(row):
    if row is None or not all(k in row for k in KINDS):
        return None
    return float(sum(row[k] for k in KINDS))


def read(ctx, what, span=None):
    if what == "account":
        for row in program_span.ring_rows() or []:
            if (row.get("plane"), row.get("phase")) == (program_span.PLANE,
                                                        ACCOUNT):
                return each_second_once(row.get("a"))
        return None
    snap = program_counter.snapshot()
    if snap is None:
        return None
    if what == "span_self":
        return each_second_once(snap["by_span"].get(span))
    if what == "again_calls":
        sites = snap.get("by_site")
        if sites is None:
            return None
        return float(sum(at["again_n"] for at in sites.values()))
    raise ValueError(f"program_setup: unknown reading {what!r}")
