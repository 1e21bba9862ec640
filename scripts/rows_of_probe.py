#!/usr/bin/env python
"""Tokens to the dispatch buffer's rows at the four sparse cells' shapes, on
the chip: the kernel over runs (``token_sum_kernels.rows_of``) against the
XLA gather it replaces (``sparse_lm._rows_of``), on one plan of a router
that favours no expert, in bfloat16 and in float32, with the unweighted
token-major sum (the inverse movement) beside them.

Prints, a shape, whether the kernel's rows below ``plan.written`` are the
gather's bit for bit and each lowering's device time a call, read from a
profile of five calls (a host clock around a 0.2 ms call measures its
dispatch: PERF.md section 6, PR 42); ``sparse_lm.GATHER_NS_A_KIB`` and
``ROWS_NS_A_KIB`` are set from these. Exits 1 where a shape differs. Fails
without a TPU::

    python3 scripts/rows_of_probe.py [--seed N] [--out chiprun_out/<dir>]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# preset, sequences a micro-batch (the cells' traffic files)
CELLS = (("smallthinker21b", 2), ("lfm2moe", 1), ("trinitymini", 1),
         ("joyaiflash", 1))
CALLS = 5


def device_seconds(trace_dir: Path) -> float:
    """Busy time of device 0's operations in the newest profile there."""
    from benchmark import trace
    ops = trace.device_ops(trace.load_xplane(trace.find_xplane(trace_dir)))
    # leaf operations only: a fusion's event encloses nothing here
    return sum(e[2] for e in ops[min(ops)]) * 1e-9


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import token_sum_kernels as token_sum

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    out = {"device": device.device_kind, "seed": args.seed, "cells": {}}
    same = True
    for c, (preset, micro) in enumerate(CELLS):
        cfg = MODEL_PRESETS[preset]()
        n, d, held = micro * cfg.total_seq_len, cfg.hidden_size, cfg.experts_held
        keys = jax.random.split(jax.random.PRNGKey(args.seed + c), 2)
        _, idx = jax.lax.top_k(jax.random.normal(keys[0], (n, cfg.num_experts)),
                               cfg.experts_per_token)
        plan = jax.jit(sparse_lm.dispatch_plan, static_argnums=(1, 2, 3))(
            idx, cfg.expert_offset, held, sparse_lm.dispatch_rows(n, cfg))
        rows, written = plan.token.shape[0], int(plan.written)
        said = {"tokens": n, "width": d, "held": held, "rows": rows,
                "written": written, "assignments": int(jnp.sum(plan.valid)),
                "spills": int(token_sum.spills(plan.start)), "us_a_call": {}}
        lowerings = {
            "gather": jax.jit(lambda s: sparse_lm._rows_of(s, plan)),
            "kernel": jax.jit(lambda s: token_sum.rows_of(
                s, plan.row_of, plan.start, plan.written, rows=rows)),
            "sum": jax.jit(lambda r: token_sum.token_major_sum(
                r, plan.row_of, plan.start, plan.written,
                out_dtype=r.dtype))}
        for dtype in ("bfloat16", "float32"):
            source = jax.random.normal(keys[1], (n, d)).astype(dtype)
            want = lowerings["gather"](source)
            got = lowerings["kernel"](source)
            equal = bool(jnp.array_equal(want[:written], got[:written]))
            said[f"equal_{dtype}"] = equal
            same &= equal
            for name, fn in lowerings.items():
                operand = want if name == "sum" else source
                jax.block_until_ready(fn(operand))
                with tempfile.TemporaryDirectory() as tmp:
                    with jax.profiler.trace(tmp):
                        for _ in range(CALLS):
                            jax.block_until_ready(fn(operand))
                    said["us_a_call"][f"{name}_{dtype}"] = round(
                        device_seconds(Path(tmp)) / CALLS * 1e6, 1)
        # the rule's two units: a KiB the gather writes (the buffer's
        # rows), a KiB of the windows the kernel writes
        tiles = -(-n // token_sum.tokens_tile(n))
        us = said["us_a_call"]
        for name, of in (("gather", rows),
                         ("kernel", tiles * held * token_sum.WINDOW)):
            said[f"{name}_ns_a_kib"] = {
                dt: round(us[f"{name}_{dt}"] * 1e3 / (of * d * size / 1024), 2)
                for dt, size in (("bfloat16", 2), ("float32", 4))}
        out["cells"][preset] = said
        print(json.dumps({preset: said}), flush=True)
    out["same"] = same
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "rows_of_probe.json").write_text(
            json.dumps(out, indent=1))
    print(json.dumps({"same": same}))
    sys.exit(0 if same else 1)


if __name__ == "__main__":
    main()
