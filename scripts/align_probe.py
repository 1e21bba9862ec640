#!/usr/bin/env python
"""The indexer's alignment alone at the ``keyevl2`` cell's size, on the chip:
the two forms of the heads' mean's kernel
(``causal_attention_kernels.selected_loss_rows``, a layer's forward pass:
the loss's rows summed where the mean's tiles are made;
``selected_mean_probs``, its backward rule: the mean alone) on the selection
and statistics the cell's own kernels make of seeded operands (B 1, T 8 192,
32 / 4 heads of 128, ``index_topk`` 2 048).

Prints the rows' KL, log-sum-exp and count against the same sums as XLA code
in f32 over the mean's (T, T) array (``log_softmax`` and the dense KL, what
``dense_selected_attention`` differentiates), and each one's device time a
call, read from a profile of five calls (a host clock around a 2 ms call
measures its dispatch too: PERF.md section 6, PR 42): the rows form, the
mean form, and the mean with those XLA sums after it. Exits 1 where a count
differs or a sum lies further from XLA's than f32 sums of 8 192 terms do.
Fails without a TPU::

    python3 scripts/align_probe.py [--seed N] [--out chiprun_out/<dir>]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CALLS = 5


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
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.config import keyevl2_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
    from dalle_tpu.ops.pallas import indexer_kernels

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = keyevl2_model_config()
    t, topk, d = cfg.total_seq_len, cfg.index_topk, cfg.head_dim
    width = cfg.index_heads * cfg.index_head_dim
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    bf16 = lambda key, lanes: jax.random.normal(key, (1, t, lanes),
                                                jnp.bfloat16)
    q, k, v = (bf16(keys[0], cfg.num_heads * d),
               bf16(keys[1], cfg.num_kv_heads * d),
               bf16(keys[2], cfg.num_kv_heads * d))
    qi, ki = bf16(keys[3], width), bf16(keys[4], cfg.index_head_dim)
    w = jax.random.normal(keys[5], (1, t, cfg.index_heads), jnp.float32)
    sel = jax.jit(lambda: indexer_kernels.index_select(
        indexer_kernels.index_scores(qi, ki, w, width ** -0.5),
        topk))()[:, :t, :t]
    _, stats = jax.jit(kernels.selected_forward)(q, k, v, sel)

    def xla_rows(sel, pbar):
        """(kl, lse, count), (B, T) each: XLA code over two (T, T) arrays."""
        on = sel > sparse_lm.OFF
        x = jnp.where(on, sel, sparse_lm.OFF)
        pbar = jnp.where(on, pbar, 0.0)
        kl = jnp.sum(jnp.where(on, jax.scipy.special.xlogy(pbar, pbar)
                               - pbar * jax.nn.log_softmax(x, axis=-1), 0.0),
                     axis=-1)
        return kl, jax.nn.logsumexp(x, axis=-1), jnp.sum(
            on, axis=-1, dtype=jnp.float32)

    forms = {
        "rows": jax.jit(kernels.selected_loss_rows),
        "mean": jax.jit(kernels.selected_mean_probs),
        "mean + XLA sums": jax.jit(lambda q, k, stats, sel: xla_rows(
            sel, kernels.selected_mean_probs(q, k, stats, sel))),
    }
    operands = (q, k, stats, sel)
    rows = np.asarray(forms["rows"](*operands))[0, :t]
    want = [np.asarray(x)[0] for x in forms["mean + XLA sums"](*operands)]
    got = [rows[:, lane] for lane in (kernels.KL_LANE, kernels.LSE_LANE,
                                      kernels.COUNT_LANE)]
    apart = {name: float(np.abs(ours - theirs).max())
             for name, ours, theirs in zip(("kl", "lse", "count"), got, want)}
    same = bool(apart["count"] == 0 and apart["kl"] < 1e-4
                and apart["lse"] < 1e-4 and got[0].min() > -1e-5
                and np.array_equal(got[2],
                                   np.minimum(np.arange(t) + 1, topk)))
    out = {"device": device.device_kind, "seed": args.seed,
           "shape": [1, t, t], "index_topk": topk, "same": same,
           "max_abs_apart": apart, "kl_sum": float(got[0].sum()),
           "kl_sum_xla": float(want[0].sum()), "kl_min": float(got[0].min())}
    print(json.dumps(out), flush=True)

    ms = {}
    for name, fn in forms.items():
        jax.block_until_ready(fn(*operands))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    jax.block_until_ready(fn(*operands))
            ops = device_seconds(Path(tmp))
        ms[name] = {op: round(s / CALLS * 1e3, 4) for op, s in ops.items()}
        ms[name]["total"] = round(sum(ops.values()) / CALLS * 1e3, 4)
        print(name, json.dumps(ms[name]), flush=True)
    out["ms_a_call"] = ms
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "times.json").write_text(json.dumps(out, indent=1))
    sys.exit(0 if same else 1)


if __name__ == "__main__":
    main()
