#!/usr/bin/env python
"""A traced benchmark run's device seconds A STEP under a module's scopes,
split by pass: the first forward, a rematerialised layer's replay (the scope
path holds ``rematted_computation``) and the backward (``transpose(jvp(``),
Mosaic kernels apart from XLA code. Reads the ``device_scopes.json`` a
``--trace 1`` run of ``benchmark.run --out-dir <dir>`` leaves (its largest
(operation, scope path, seconds) rows over the traced steps)::

    python3 scripts/scopes_by_pass.py <dir>/device_scopes.json [--under gdn]

and ends with the Mosaic kernels that run inside a replay: a layer that keeps
what a kernel made does not list it (PERF.md section 5,
``qwen3next80b-train-solo``, PR 66).
"""

from __future__ import annotations

import argparse
import collections
import json
import re

TRACED_STEPS = 3
PASSES = ("forward", "replay", "backward")


def pass_of(scope: str) -> str:
    if "rematted_computation" in scope:
        return "replay"
    return "backward" if "transpose(jvp(" in scope else "forward"


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("scopes")
    parser.add_argument("--under", default="gdn",
                        help="the module whose scopes are listed")
    args = parser.parse_args(argv)
    with open(args.scopes) as f:
        rows = json.load(f)
    named = re.compile(rf"/{re.escape(args.under)}/(\w+)")
    table = collections.defaultdict(collections.Counter)
    replayed = set()
    for op, scope, seconds in rows:
        found = named.search(scope)
        if not found:
            continue
        mosaic, which = op.endswith("[mosaic]"), pass_of(scope)
        table[f"{args.under}/{found.group(1)} "
              + (op if mosaic else "XLA code")][which] += seconds
        if mosaic and which == "replay":
            replayed.add(op)
    print(f"{'s a step':36s}" + "".join(f"{p:>10s}" for p in PASSES))
    for what in sorted(table):
        print(f"{what:36s}" + "".join(
            f"{table[what][p] / TRACED_STEPS:10.4f}" for p in PASSES))
    print("Mosaic kernels inside a replay:", ", ".join(sorted(replayed)))


if __name__ == "__main__":
    main()
