"""Benchmark harness: flagship DALL-E train-step throughput, images/sec/chip.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "device": {platform, kind, count}, "rung": {micro, accum, ...}}
and only on a TPU: without one it exits non-zero and prints no row.

The reference (learning-at-home/dalle) publishes no numbers (README.md:1-17;
BASELINE.json "published": {}), so the baseline is the north-star target from
BASELINE.json: >=30 images/sec/chip for DALL-E-1.3B. ``vs_baseline`` is
value / 30.

What is measured: the sustained training regime — ``accum_steps``
microbatches accumulated on device followed by one LAMB-8bit update, all
inside a single jitted train step (training-parity configuration: remat on,
bf16 activations, fp32 params, Pallas fused axial attention). This mirrors
how the framework actually trains: the reference accumulates toward
``target_batch_size`` and steps the (offloaded, 8-bit) LAMB once per swarm
epoch (``arguments.py:62-65``), so the optimizer cost amortizes over the
accumulated batch rather than being paid per microbatch.
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_IMAGES_PER_SEC_PER_CHIP = 30.0
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM", "Allocation failure", "exceeds the limit")


def _is_oom(err: Exception) -> bool:
    return any(m in str(err) for m in _OOM_MARKERS)


def _bench(model_cfg, per_chip_micro: int, accum: int, warmup: int,
           iters: int) -> float:
    """Images/sec/chip for the jitted, mesh-sharded accumulate+update train
    step over ALL local devices (dp over chips, like
    __graft_entry__.dryrun_multichip)."""
    import jax

    from dalle_tpu.config import OptimizerConfig
    from dalle_tpu.data.synthetic import SyntheticCodes
    from dalle_tpu.models.dalle import DALLE, init_params
    from dalle_tpu.optim import make_optimizer
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.parallel.sharding import shard_train_state
    from dalle_tpu.training.steps import TrainState, make_train_step

    n_chips = jax.local_device_count()
    mesh = make_mesh(dp=-1)
    batch_size = per_chip_micro * accum * n_chips

    model = DALLE(model_cfg, mesh=mesh)
    params = init_params(model, jax.random.PRNGKey(0))
    tx = make_optimizer(OptimizerConfig(warmup_steps=10, total_steps=1000),
                        mesh=mesh)
    state = shard_train_state(mesh, TrainState.create(params, tx))

    data = SyntheticCodes(model_cfg, num_samples=batch_size, seed=0)
    batch = next(data.batches(batch_size, seed=0))
    batch = jax.device_put(batch, batch_sharding(mesh))

    step = jax.jit(make_train_step(model, tx, accum_steps=accum),
                   donate_argnums=0)

    def run(n: int) -> float:
        """n chained steps; returns the final loss. The device_get of the
        scalar forces completion of the whole chain."""
        nonlocal state
        metrics = None
        for _ in range(n):
            state, metrics = step(state, batch)
        return float(jax.device_get(metrics["loss"]))

    run(warmup)
    t0 = time.perf_counter()
    final_loss = run(iters)
    dt = time.perf_counter() - t0
    assert final_loss == final_loss, "NaN loss in benchmark"
    return (batch_size * iters) / dt / n_chips


def main() -> None:
    import jax

    from dalle_tpu.config import flagship_model_config
    from dalle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        # a rate is a device metric: without the chip there is nothing to
        # measure, and a CPU number must never appear under its name
        sys.exit(f"bench.py needs a TPU (jax.default_backend() is "
                 f"{jax.default_backend()!r}): no row printed")

    # Walk configurations down on OOM; anything that is not an OOM is a
    # real bug and propagates. Best measured (PERF.md): partial remat (1
    # of 4 shared blocks un-rematerialized) + streaming cross-entropy at
    # microbatch 4 — the un-rematted block's activations fit in HBM at
    # micro 4 and remove 1/4 of the remat recompute, and the chunked-
    # logsumexp head never materializes the (B, T, 8192) logits (micro 8
    # + skip OOMs even with the streamed head; plain micro 8 is next).
    # flagship_model_config already carries the tuned knobs
    # (config.FLAGSHIP_TUNED) — the fallback rungs must explicitly drop
    # the partial remat, which COSTS memory (the fallbacks exist because
    # memory ran out). accum 128 (512 samples/peer/epoch — an 8-peer
    # share of the swarm's 4096-sample epoch) amortizes the LAMB apply
    # further: at the r5 save_attn+hoist config it measured 11.735 vs
    # 11.599 (PERF_GRID.json).
    row = None
    regime_rows = {}
    for micro, accum, overrides in (
            (4, 128, {}),
            (4, 64, {}),
            (4, 32, {}),
            (8, 16, {"remat_skip_blocks": 0}),
            (4, 16, {"remat_skip_blocks": 0}),
            (2, 16, {"remat_skip_blocks": 0}),
            (1, 8, {"remat_skip_blocks": 0})):
        cfg = flagship_model_config(**overrides)
        try:
            ips = _bench(cfg, micro, accum, warmup=1, iters=3)
        except Exception as e:  # noqa: BLE001 - re-raised unless OOM
            if not _is_oom(e):
                raise
            # full first line of the error so a genuine compile bug
            # misclassified as OOM is still visible in driver logs
            msg = (str(e).splitlines() or [repr(e)])[0]
            print(f"# micro {micro} {overrides} walked down: "
                  f"{type(e).__name__}: {msg[:300]}", file=sys.stderr)
            continue
        device = jax.devices()[0]
        row = {
            "metric": "dalle-1.3b train images/sec/chip (tpu)",
            "value": round(ips, 3),
            "unit": "images/sec/chip",
            "vs_baseline": round(ips / BASELINE_IMAGES_PER_SEC_PER_CHIP, 4),
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": len(jax.devices())},
            # the ladder rung that ran: a walked-down row is another regime
            "rung": {"micro": micro, "accum": accum, **overrides},
        }
        regime_rows[f"accum{accum}"] = round(ips, 3)
        # Pin the bench regime (VERDICT r5 weak #6: the r4->r5 headline
        # mixed an accum 64->128 change into the code delta): when the
        # headline lands at accum 128, also measure the SAME code at
        # accum 64 so round-over-round comparisons have a regime-matched
        # row on both sides.
        if accum == 128:
            try:
                regime_rows["accum64"] = round(
                    _bench(cfg, micro, 64, warmup=1, iters=3), 3)
            except Exception as e:  # noqa: BLE001 - OOM only
                if not _is_oom(e):
                    raise
        break
    if row is None:
        sys.exit("bench.py: no ladder rung fit in device memory")
    if len(regime_rows) > 1:
        row["regime_rows"] = regime_rows
    print(json.dumps(row))


if __name__ == "__main__":
    main()
