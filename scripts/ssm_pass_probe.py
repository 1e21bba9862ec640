#!/usr/bin/env python
"""The Mamba-2 mixer's two passes alone at the ``twotower30b`` cell's size,
on the chip: the taps pass and the gate-and-norm pass
(``ssm_pass_kernels.taps_silu`` / ``gate_norm``) beside the XLA code they
replace (``sparse_lm.causal_taps_silu`` on a slice and three slices of its
result; ``sparse_lm.gated_group_norm`` on a slice) on seeded operands (B 1,
T 8 192, ``in_proj``'s (T, 10 304) output in bfloat16, f32 taps, bias and
scale).

Prints how far each pass's results and gradients (through a
``jax.checkpoint``ed call: the forward, its replay and the backward) lie
from the XLA code's and from the same expression in f32, and each one's
device time a call by operation, read from a profile of five calls.
``--rows`` / ``--chunk`` / ``--slab`` / ``--norm-chunk`` try other values
of the kernels' constants, each combination a line. Exits 1 where a pass is further from
the f32 numbers than ``--within`` and than 1.5 times the XLA code's own
distance. Fails without a TPU::

    python3 scripts/ssm_pass_probe.py [--seed N] [--out chiprun_out/<dir>]
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


def device_seconds(trace_dir: Path) -> dict:
    """The device's self seconds in the newest profile there, by
    operation."""
    from benchmark import trace
    reduced = trace.Reduced(trace.load_xplane(trace.find_xplane(trace_dir)))
    return {trace.op_key(name): seconds
            for name, seconds in reduced.seconds_by_name().items() if seconds}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ints = lambda s: [int(x) for x in s.split(",")]
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--within", type=float, default=0.01)
    parser.add_argument("--rows", default=None, type=ints)
    parser.add_argument("--chunk", default=None, type=ints)
    parser.add_argument("--slab", default=None, type=ints)
    parser.add_argument("--norm-chunk", default=None, type=ints)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from dalle_tpu.config import twotower30b_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import ssm_pass_kernels as K

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = twotower30b_model_config()
    t, inner, groups = cfg.total_seq_len, cfg.mamba_inner, cfg.ssm_groups
    state, lanes = groups * cfg.ssm_state_size, cfg.mamba_conv_lanes
    widths = (inner, state, state)
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    zxbcdt = jax.random.normal(
        keys[0], (1, t, inner + lanes + cfg.mamba_num_heads), dtype)
    taps = 0.5 * jax.random.normal(keys[1], (cfg.conv_kernel, lanes))
    bias = 0.1 * jax.random.normal(keys[2], (lanes,))
    y = jax.random.normal(keys[3], (1, t, inner), dtype)
    scale = 1.0 + 0.1 * jax.random.normal(keys[4], (inner,))
    weigh = [jax.random.normal(k, (1, t, w), dtype)
             for k, w in zip(keys[5:], widths)]

    def taps_xla(zxbcdt, taps, bias):
        xbc = sparse_lm.causal_taps_silu(zxbcdt[..., inner:inner + lanes],
                                         taps, bias)
        return (xbc[..., :inner], xbc[..., inner:inner + state],
                xbc[..., inner + state:])

    def taps_kernel(zxbcdt, taps, bias):
        return K.taps_silu(zxbcdt, taps, bias, inner, widths)

    def norm_xla(y, zxbcdt, scale):
        return (sparse_lm.gated_group_norm(y, zxbcdt[..., :inner], scale,
                                           groups, cfg.rms_eps),)

    def norm_kernel(y, zxbcdt, scale):
        return (K.gate_norm(y, zxbcdt, scale, groups, cfg.rms_eps),)

    def both(fn, scope):
        def forward(*o):
            with jax.named_scope(scope):
                return fn(*o)

        def loss(*o):
            outs = jax.checkpoint(forward)(*o)
            return sum(jnp.sum(out.astype(jnp.float32) * w)
                       for out, w in zip(outs, weigh))
        return jax.jit(forward), jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def timed(fn, operands):
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

    def distances(got, want):
        return [round(apart(a, b), 7) for a, b in zip(
            jax.tree.leaves(got), jax.tree.leaves(want))]

    stages = {
        "taps": (taps_xla, taps_kernel, (zxbcdt, taps, bias), "conv"),
        "gate_norm": (norm_xla, norm_kernel, (y, zxbcdt, scale),
                      "gate_norm")}
    out = {"device": device.device_kind, "seed": args.seed,
           "zxbcdt": list(zxbcdt.shape), "dtype": str(dtype), "stages": {}}
    near = True
    names = ("ROWS", "CHUNK", "SLAB", "NORM_CHUNK")
    shipped = tuple(getattr(K, name) for name in names)
    tried = list(itertools.product(*(
        asked or [was] for asked, was in zip(
            (args.rows, args.chunk, args.slab, args.norm_chunk), shipped))))

    def set_constants(values):
        for name, value in zip(names, values):
            setattr(K, name, value)
        jax.clear_caches()
    for name, (xla, kernel, operands, scope) in stages.items():
        fwd, grad = both(xla, scope)
        want = (fwd(*operands), grad(*operands))
        exact = tuple(o.astype(jnp.float32) for o in operands)
        true = (fwd(*exact), grad(*exact))
        said = {"xla_from_f32": distances(want, true),
                "xla_ms_forward": timed(fwd, operands),
                "xla_ms_replay_and_backward": timed(grad, operands),
                "tried": []}
        print(json.dumps({name: said}), flush=True)
        for constants in tried:
            if name == "taps" and constants[:3] in [
                    x["constants"][:3] for x in said["tried"]]:
                continue                    # the norm's chunk is not its
            set_constants(constants)
            line = {"constants": constants}
            try:
                fwd, grad = both(kernel, scope)
                got = (fwd(*operands), grad(*operands))
                line["from_xla"] = distances(got, want)
                line["from_f32"] = distances(got, true)
                if constants == shipped:
                    near &= all(
                        d < max(args.within, 1.5 * x) for d, x in zip(
                            line["from_f32"], said["xla_from_f32"]))
                line["ms_forward"] = timed(fwd, operands)
                line["ms_replay_and_backward"] = timed(grad, operands)
            except Exception as e:                  # a refusal of Mosaic's
                line["failed"] = str(e)[-800:]
                near &= constants != shipped
            said["tried"].append(line)
            print(json.dumps(line), flush=True)
        set_constants(shipped)
        out["stages"][name] = said
    out["near"] = near
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "passes.json").write_text(json.dumps(out, indent=1))
    print(json.dumps({"near": near}))
    sys.exit(0 if near else 1)


if __name__ == "__main__":
    main()
