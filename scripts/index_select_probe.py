#!/usr/bin/env python
"""The indexer's selection alone at the ``keyevl2`` cell's size, on the chip:
the kernel (``indexer_kernels.index_select``) beside the XLA code it
replaces (``sparse_lm.select_keys``) and ``lax.top_k``, on the scores
``index_scores`` makes of seeded operands (B 1, T 8 192, ``index_topk``
2 048) and on the same scores with ties planted (rows of few values, exact
zeros, a ``-0.0`` column, the largest and least finite f32).

Prints whether the kernel's array is ``select_keys``' bit for bit on both,
whether sampled rows are ``lax.top_k``'s sets, and each one's device time a
call, read from a profile of five calls (a host clock around a 1 ms call
measures its dispatch too: PERF.md section 6, PR 42), the kernel's also
where every row chooses every key (``index_topk`` = T: no search, the
block's copies in and out alone). ``--rows`` and ``--turn`` try other values
of the kernel's two constants, each combination a line. Exits 1 where an
array differs. Fails without a TPU::

    python3 scripts/index_select_probe.py [--seed N] [--out chiprun_out/<dir>]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 5
# the rows whose sets are compared with ``lax.top_k``'s: the last that
# chooses every key, the first that searches, block edges, the last
ROWS = (0, 2047, 2048, 2049, 2111, 4095, 4096, 6143, 8191)


def device_seconds(trace_dir: Path) -> dict:
    """The device's self seconds in the newest profile there, by operation
    (a loop's event is charged what its body's do not cover)."""
    from benchmark import trace
    reduced = trace.Reduced(trace.load_xplane(trace.find_xplane(trace_dir)))
    return {trace.op_key(name): seconds
            for name, seconds in reduced.seconds_by_name().items() if seconds}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None)
    ints = lambda s: [int(x) for x in s.split(",")]
    parser.add_argument("--rows", type=ints, default=None)
    parser.add_argument("--turn", type=ints, default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.config import keyevl2_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import indexer_kernels

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = keyevl2_model_config()
    t, topk, chunk = cfg.total_seq_len, cfg.index_topk, cfg.index_chunk
    width = cfg.index_heads * cfg.index_head_dim
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    qi = jax.random.normal(keys[0], (1, t, width), jnp.bfloat16)
    ki = jax.random.normal(keys[1], (1, t, cfg.index_head_dim), jnp.bfloat16)
    w = jax.random.normal(keys[2], (1, t, cfg.index_heads), jnp.float32)
    scale = width ** -0.5

    scores_of = jax.jit(lambda: indexer_kernels.index_scores(qi, ki, w,
                                                             scale))

    @jax.jit
    def tied():
        """The scores with ties planted, a band of rows a kind."""
        x = scores_of()
        row = jnp.arange(t)[:, None]
        few = jnp.round(x * 2.0) * 0.5              # five or six values
        x = jnp.where((row % 1024 >= 960), few, x)
        x = jnp.where((row % 1024 < 32) & (x < 0.3), 0.0, x)   # zeros
        x = x.at[:, :, 3].set(-0.0)
        big = float(np.finfo(np.float32).max)
        x = x.at[:, :, 5].set(big).at[:, :, 6].set(-big)
        return x.at[:, :, 4000:4100].set(big)      # a tie at the top

    xla = jax.jit(lambda s: sparse_lm.select_keys(s, topk, chunk))

    def kernel(topk=topk):
        def select(s):
            with jax.named_scope("select"):
                return indexer_kernels.index_select(s, topk)
        return jax.jit(select, donate_argnums=0)

    def timed(fn, make):
        jax.block_until_ready(fn(make()))
        with tempfile.TemporaryDirectory() as tmp:
            operands = [jax.block_until_ready(make()) for _ in range(CALLS)]
            with jax.profiler.trace(tmp):
                for operand in operands:
                    jax.block_until_ready(fn(operand))
            del operands
            ops = device_seconds(Path(tmp))
        return {name: round(s / CALLS * 1e3, 4) for name, s in ops.items()}

    out = {"device": device.device_kind, "seed": args.seed,
           "shape": [1, t, t], "index_topk": topk}
    ms = {}
    ms["index_scores"] = timed(lambda _: scores_of(), lambda: None)
    ms["select_keys"] = timed(xla, scores_of)
    ms["lax.top_k 512 rows"] = timed(
        jax.jit(lambda s: jax.lax.top_k(s[:, :512], topk)[1]), scores_of)
    print(json.dumps(ms), flush=True)

    want = {name: jax.block_until_ready(xla(make()))
            for name, make in (("scores", scores_of), ("tied", tied))}
    # ``lax.top_k``'s sets on the sampled rows of both arrays
    sets_same = True
    for name, make in (("scores", scores_of), ("tied", tied)):
        x = make()
        for r in ROWS:
            _, idx = jax.lax.top_k(x[0, r, :r + 1], min(topk, r + 1))
            on = np.zeros(t, bool)
            on[np.asarray(idx)] = True
            # (the least finite f32 lies under ``OFF``: told by equality)
            sets_same &= bool((np.asarray(want[name][0, r] != sparse_lm.OFF)
                               == on).all())
    out["select_keys_is_top_k_on_sampled_rows"] = sets_same

    same = sets_same
    tried = []
    defaults = (indexer_kernels.SELECT_ROWS, indexer_kernels.SELECT_TURN)
    for rows, turn in itertools.product(args.rows or defaults[:1],
                                        args.turn or defaults[1:]):
        indexer_kernels.SELECT_ROWS, indexer_kernels.SELECT_TURN = rows, turn
        line = {"rows": rows, "turn": turn}
        try:
            fn = kernel()
            for name, make in (("scores", scores_of), ("tied", tied)):
                line[f"equal_{name}"] = bool(jnp.array_equal(
                    fn(make()), want[name]))
                same &= line[f"equal_{name}"]
                line[f"ms_{name}"] = timed(fn, make)
            line["ms_no_search"] = timed(kernel(t), scores_of)
        except Exception as e:                      # a refusal of Mosaic's
            line["failed"] = str(e)[-800:]
        tried.append(line)
        print(json.dumps(line), flush=True)
    indexer_kernels.SELECT_ROWS, indexer_kernels.SELECT_TURN = defaults
    shipped = [x for x in tried if (x["rows"], x["turn"]) == defaults
               and "ms_no_search" in x]
    if shipped:
        ms["index_select"] = shipped[0]["ms_scores"]
        ms["index_select, ties planted"] = shipped[0]["ms_tied"]
        ms["index_select, no search"] = shipped[0]["ms_no_search"]
    out.update(same=same, ms_a_call=ms, tried=tried)
    # seconds a call under ``pr52_time``'s keys: an operation's own
    out["times"] = {name: round(sum(ops.values()) * 1e-3, 7)
                    for name, ops in ms.items()}
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "times.json").write_text(json.dumps(out, indent=1))
    print(json.dumps({"same": same, "times": out["times"]}))
    sys.exit(0 if same else 1)


if __name__ == "__main__":
    main()
