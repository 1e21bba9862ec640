#!/usr/bin/env python
"""The grouped kernels of the expert layer alone, at the four sparse cells'
shapes and tile counts, on the chip: the three products a direction with the
XLA code between them (``old``: every row-tile BlockSpec maps a grid step to
its own tile, so a tile that holds no row is fetched and written back all the
same, as before PR 50), the same three with the inactive tiles unmoved
(``unmoved``), and the expert block on the tile (``block``:
``gated_hidden``, ``gated_hidden_grads``, ``rows_grad`` and three
``weights_grad``), on one plan of a router that favours no expert.

Prints, a shape, each form's device time a call (from a profile of five
calls: a host clock around a 0.3 ms call measures its dispatch) beside what
the active tiles' products take at the MXU's peak and what the bytes every
kernel has to move take at the HBM's, and how far the block's results lie
from the three products' on the chip (``g`` and ``u`` are the same products:
equal; the activation is Mosaic's there and XLA's here). The docstring of
``ops/pallas/grouped_matmul_kernels.py`` has the readings. Fails without a
TPU::

    python3 scripts/grouped_probe.py [--seed N] [--out chiprun_out/<dir>]
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
CELLS = (("lfm2moe", 1), ("smallthinker21b", 2), ("trinitymini", 1),
         ("joyaiflash", 1))
CALLS = 5
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # one v5e chip, bf16 (PERF.md)


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
    from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")

    def timed(fn, *operands) -> float:
        """Device microseconds a call."""
        jax.block_until_ready(fn(*operands))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    jax.block_until_ready(fn(*operands))
            return round(device_seconds(Path(tmp)) / CALLS * 1e6, 1)

    out = {"device": device.device_kind, "seed": args.seed, "cells": {}}
    for c, (preset, micro) in enumerate(CELLS):
        cfg = MODEL_PRESETS[preset]()
        n, d, f = micro * cfg.total_seq_len, cfg.hidden_size, cfg.expert_width
        held, name, dt = cfg.experts_held, cfg.hidden_act, jnp.dtype(cfg.dtype)
        keys = jax.random.split(jax.random.PRNGKey(args.seed + c), 8)
        _, idx = jax.lax.top_k(
            jax.random.normal(keys[0], (n, cfg.num_experts)),
            cfg.experts_per_token)
        plan = jax.jit(sparse_lm.dispatch_plan, static_argnums=(1, 2, 3))(
            idx, cfg.expert_offset, held, sparse_lm.dispatch_rows(n, cfg))
        tiles = plan.tiles
        every = tiles._replace(block=jnp.arange(tiles.block.shape[0],
                                                dtype=jnp.int32))
        rows, n_tiles = plan.token.shape[0], int(tiles.block.shape[0])
        active = int(jnp.sum(tiles.active))
        on_rows = lambda key, width: jnp.where(
            plan.valid[:, None], jax.random.normal(key, (rows, width)),
            0).astype(dt)
        xs, dys = on_rows(keys[1], d), on_rows(keys[2], d) * 0.1
        weights = lambda key, *shape: (jax.random.normal(key, shape)
                                       * shape[1] ** -0.5).astype(dt)
        gate, up = weights(keys[3], held, d, f), weights(keys[4], held, d, f)
        down = weights(keys[5], held, f, d)

        def three_forward(tiles):
            def forward(xs, gate, up, down):
                g = grouped.grouped_matmul(xs, gate, tiles)
                u = grouped.grouped_matmul(xs, up, tiles)
                hidden = grouped.act(name, g) * u
                return g, u, hidden, grouped.grouped_matmul(hidden, down,
                                                            tiles)
            return jax.jit(forward)

        def three_backward(tiles):
            def backward(xs, g, u, dys, gate, up, down):
                grads = lambda *a: grouped.grouped_matmul_grads(*a, tiles)
                hidden = grouped.act(name, g)
                dhidden, ddown = grads(hidden * u, down, dys)
                dg = grouped.gate_cotangent(name, g, u, dhidden)
                du = dhidden * hidden
                dxs_gate, dgate = grads(xs, gate, dg)
                dxs_up, dup = grads(xs, up, du)
                return dg, du, dxs_gate + dxs_up, dgate, dup, ddown
            return jax.jit(backward)

        @jax.jit
        def block_forward(xs, gate, up, down):
            g, u, hidden = grouped.gated_hidden(xs, gate, up, tiles, name)
            return g, u, hidden, grouped.grouped_matmul(hidden, down, tiles)

        @jax.jit
        def block_backward(xs, g, u, dys, gate, up, down):
            dg, du, hidden = grouped.gated_hidden_grads(dys, down, g, u,
                                                        tiles, name)
            dgate, dup, ddown = (
                grouped.weights_grad(x, dy, tiles, w)
                for x, dy, w in ((xs, dg, gate), (xs, du, up),
                                 (hidden, dys, down)))
            return (dg, du, grouped.rows_grad(dg, du, gate, up, tiles), dgate,
                    dup, ddown)

        forward = (xs, gate, up, down)
        g, u, _, _ = three_forward(tiles)(*forward)
        backward = (xs, g, u, dys, gate, up, down)
        one = {
            "gmm": lambda t: jax.jit(
                lambda x, w: grouped.grouped_matmul(x, w, t)),
            "gmm_transposed": lambda t: jax.jit(
                lambda dy, w: grouped.grouped_matmul(dy, w, t,
                                                     transpose_w=True)),
            "tgmm": lambda t: jax.jit(
                lambda x, dy, w: grouped.weights_grad(x, dy, t, w))}
        of_one = {"gmm": (xs, gate), "gmm_transposed": (g, gate),
                  "tgmm": (xs, g, gate)}
        us = {}
        for form, t in (("old", every), ("unmoved", tiles)):
            for kernel, build in one.items():
                us[f"{kernel}.{form}"] = timed(build(t), *of_one[kernel])
            us[f"forward.{form}"] = timed(three_forward(t), *forward)
            us[f"backward.{form}"] = timed(three_backward(t), *backward)
        us["forward.block"] = timed(block_forward, *forward)
        us["backward.block"] = timed(block_backward, *backward)
        us["gated_hidden"] = timed(jax.jit(
            lambda *a: grouped.gated_hidden(*a, tiles, name)), xs, gate, up)
        us["gated_hidden_grads"] = timed(jax.jit(
            lambda *a: grouped.gated_hidden_grads(*a, tiles, name)),
            dys, down, g, u)
        us["rows_grad"] = timed(jax.jit(
            lambda *a: grouped.rows_grad(*a, tiles)), g, u, gate, up)

        # least times: an active tile's product at the MXU's peak, and the
        # bytes a call has to move (its active tiles once, the held
        # experts' weights once) at the HBM's
        product = 2 * active * grouped.TILE * d * f
        tile_bytes = lambda *widths: active * grouped.TILE * sum(widths) \
            * dt.itemsize
        weight = held * d * f * dt.itemsize
        least = {
            "gmm": (product, tile_bytes(d, f) + weight),
            "tgmm": (product, tile_bytes(d, f) + weight),
            "forward": (3 * product, tile_bytes(d, f, f, f, f, d)
                        + 3 * weight),
            "backward": (6 * product, tile_bytes(
                d, f, f, f, f, f, d, f, f, f, f, d, d) + 6 * weight),
        }
        far = lambda a, b: {
            "differ_pct": round(100 * float(jnp.mean(
                (a != b)[:active * grouped.TILE])), 4),
            "rel_l2": float(jnp.linalg.norm(
                (a - b)[:active * grouped.TILE].astype(jnp.float32))
                / jnp.linalg.norm(b[:active * grouped.TILE].astype(
                    jnp.float32)))}
        rows_of = ("g", "u", "hidden", "ys")
        grads_of = ("dg", "du", "dxs")
        old_f, new_f = three_forward(tiles)(*forward), block_forward(*forward)
        old_b, new_b = (three_backward(tiles)(*backward),
                        block_backward(*backward))
        said = {
            "tokens": n, "dim": d, "width": f, "held": held, "act": name,
            "rows": rows, "tiles": n_tiles, "tiles_active": active,
            "us_a_call": us,
            "at_peak_us": {k: round(v[0] / PEAK_FLOPS * 1e6, 1)
                           for k, v in least.items()},
            "bytes_us": {k: round(v[1] / PEAK_BYTES * 1e6, 1)
                         for k, v in least.items()},
            "block_against_three": {
                **{k: far(a, b) for k, a, b in zip(rows_of, new_f, old_f)},
                **{k: far(a, b) for k, a, b in zip(grads_of, new_b, old_b)},
                **{k: {"rel_l2": float(jnp.linalg.norm(
                    (a - b).astype(jnp.float32)) / jnp.linalg.norm(
                        b.astype(jnp.float32)))}
                   for k, a, b in zip(("dgate", "dup", "ddown"), new_b[3:],
                                      old_b[3:])}}}
        out["cells"][preset] = said
        print(json.dumps({preset: said}), flush=True)
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        (Path(args.out) / "grouped_probe.json").write_text(
            json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
