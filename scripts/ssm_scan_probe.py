#!/usr/bin/env python
"""The Mamba-2 chunked scan alone at the ``twotower30b`` cell's size, on the
chip: the kernel pair (``ssm_scan_kernels.scan``) beside the XLA code it
replaces (``sparse_lm.chunked_scan``) on seeded operands drawn as a mixer's
are (B 1, T 8 192, 64 heads of 64 in 8 groups, state 128, chunks of 128,
bfloat16, ``dt`` a softplus, ``a`` from -16 to -1).

Prints how far the kernel's ``y`` and each operand's gradient (through a
``jax.checkpoint``ed call: the forward, its replay and the backward) lie
from the XLA code's and from the same expression in f32, and each one's
device time a call, read from a
profile of five calls (a host clock around a 1 ms call measures its
dispatch too: PERF.md section 6, PR 42). ``--step-tokens`` tries other
values of the kernels' one constant, each a line. Exits 1 where the kernel
is further from the f32 numbers than ``--within`` and than 1.5 times the
XLA code's own distance. Fails without a TPU::

    python3 scripts/ssm_scan_probe.py [--seed N] [--out chiprun_out/<dir>]
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
OPERANDS = ("x", "B", "C", "dt", "a", "d")


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
    parser.add_argument("--within", type=float, default=0.02)
    parser.add_argument("--step-tokens", default=None,
                        type=lambda s: [int(x) for x in s.split(",")])
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dalle_tpu.config import twotower30b_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import ssm_scan_kernels

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = twotower30b_model_config()
    t, h, g = cfg.total_seq_len, cfg.mamba_num_heads, cfg.ssm_groups
    sizes = dict(heads=h, groups=g, chunk=cfg.ssm_chunk)
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 7)
    state = g * cfg.ssm_state_size
    operands = (
        jax.random.normal(keys[0], (1, t, cfg.mamba_inner), dtype),
        jax.random.normal(keys[1], (1, t, state), dtype),
        jax.random.normal(keys[2], (1, t, state), dtype),
        jax.nn.softplus(jax.random.normal(keys[3], (1, t, h)) - 3.0),
        -jax.random.uniform(keys[4], (h,), jnp.float32, *sparse_lm.A_RANGE),
        jax.random.normal(keys[5], (h,)))
    weigh = jax.random.normal(keys[6], operands[0].shape, dtype)

    def both(scan):
        def forward(*o):
            with jax.named_scope("scan"):
                return scan(*o, **sizes)

        def loss(*o):
            y = jax.checkpoint(forward)(*o)
            return jnp.sum(y.astype(jnp.float32) * weigh)
        return jax.jit(forward), jax.jit(jax.grad(loss, argnums=tuple(
            range(len(OPERANDS)))))

    def timed(fn):
        jax.block_until_ready(fn(*operands))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    jax.block_until_ready(fn(*operands))
            ops = device_seconds(Path(tmp))
        return {name: round(s / CALLS * 1e3, 4) for name, s in ops.items()}

    def apart(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    xla_fwd, xla_grad = both(sparse_lm.chunked_scan)
    want_y, want_grads = xla_fwd(*operands), xla_grad(*operands)
    # the same numbers as f32 operands of f32 products: what both round
    with jax.default_matmul_precision("highest"):
        exact = tuple(o.astype(jnp.float32) for o in operands)
        true_y, true_grads = xla_fwd(*exact), xla_grad(*exact)

    def distances(y, grads, to_y, to_grads):
        return dict({"y": apart(y, to_y)}, **{
            f"d{name}": apart(got, want)
            for name, got, want in zip(OPERANDS, grads, to_grads)})

    out = {"device": device.device_kind, "seed": args.seed,
           "x": list(operands[0].shape), "dtype": str(dtype),
           "chunked_scan_from_f32": distances(want_y, want_grads, true_y,
                                              true_grads)}
    print(json.dumps(out), flush=True)
    ms = {"chunked_scan": timed(xla_fwd),
          "chunked_scan, replay and backward": timed(xla_grad)}
    print(json.dumps({k: round(sum(v.values()), 4) for k, v in ms.items()}),
          flush=True)
    near = True
    tried = []
    shipped = ssm_scan_kernels.STEP_TOKENS
    for step_tokens in args.step_tokens or [shipped]:
        ssm_scan_kernels.STEP_TOKENS = step_tokens
        jax.clear_caches()
        line = {"step_tokens": step_tokens}
        try:
            fwd, grad = both(ssm_scan_kernels.scan)
            y, grads = fwd(*operands), grad(*operands)
            line.update(distances(y, grads, want_y, want_grads))
            line["from_f32"] = distances(y, grads, true_y, true_grads)
            # no further from the f32 numbers than the XLA code, or near it
            near &= all(
                v < max(args.within, 1.5 * out["chunked_scan_from_f32"][k])
                for k, v in line["from_f32"].items())
            line["ms_forward"] = timed(fwd)
            line["ms_replay_and_backward"] = timed(grad)
        except Exception as e:                      # a refusal of Mosaic's
            line["failed"] = str(e)[-800:]
            near = False
        tried.append(line)
        print(json.dumps(line), flush=True)
    ssm_scan_kernels.STEP_TOKENS = shipped
    at_shipped = [x for x in tried if x["step_tokens"] == shipped
                  and "ms_forward" in x]
    if at_shipped:
        ms["scan"] = at_shipped[0]["ms_forward"]
        ms["scan, replay and backward"] = at_shipped[0][
            "ms_replay_and_backward"]
    out.update(near=near, ms_a_call=ms, tried=tried)
    out["times"] = {name: round(sum(ops.values()) * 1e-3, 7)
                    for name, ops in ms.items()}
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "times.json").write_text(json.dumps(out, indent=1))
    print(json.dumps({"near": near, "times": out["times"]}))
    sys.exit(0 if near else 1)


if __name__ == "__main__":
    main()
