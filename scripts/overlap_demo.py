"""Multi-peer overlapped-round demo (VERDICT r4 next #1's artifact).

Two real peers on loopback with a LONG matchmaking window (10 s — the
reference's Internet default is 15 s) train a tiny model through the
production CollaborativeOptimizer with ``delay_optimizer_step``: the
artifact records, per epoch, how many grad steps each peer executed
WHILE its swarm round was in flight and how much round wall was hidden
behind training. With the synchronous path those windows would be pure
device idle (the r4 sustained run measured 3 s of 26 s lost per epoch
even solo); with the overlap the chip never waits.

Run:  JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python scripts/overlap_demo.py
Appends one JSON line to OVERLAP_DEMO.json at the repo root.
"""

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import jax
    import numpy as np

    from dalle_tpu.config import CollabConfig, OptimizerConfig, \
        tiny_model_config
    from dalle_tpu.data.synthetic import SyntheticCodes
    from dalle_tpu.models.dalle import DALLE, init_params
    from dalle_tpu.optim import make_optimizer
    from dalle_tpu.swarm import DHT, Identity
    from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
    from dalle_tpu.training.steps import TrainState, make_apply_step, \
        make_grad_step

    matchmaking_time = 10.0
    epochs = 3
    cfg = CollabConfig(run_id="overlap-demo", target_batch_size=64,
                       matchmaking_time=matchmaking_time,
                       allreduce_timeout=30.0, averaging_timeout=60.0,
                       average_state_every=0,
                       delay_optimizer_step=True)
    model_cfg = tiny_model_config()
    model = DALLE(model_cfg)

    nodes = [DHT(initial_peers=[], identity=Identity.generate(),
                 rpc_timeout=2.0)]
    nodes.append(DHT(initial_peers=[nodes[0].visible_address],
                     identity=Identity.generate(), rpc_timeout=2.0))

    results = [None, None]

    def peer(i):
        # stagger the second peer: the first peer's opening round then
        # genuinely WAITS most of its matchmaking window for a straggler
        # (the reference's Internet scenario) — and trains through it
        time.sleep(i * 7.0)
        params = init_params(model, jax.random.PRNGKey(0))
        tx = make_optimizer(OptimizerConfig(warmup_steps=2,
                                            total_steps=100))
        state = TrainState.create(params, tx)
        opt = CollaborativeOptimizer(nodes[i], cfg, state,
                                     jax.jit(make_apply_step(tx)))
        opt.tracker.min_refresh_period = 0.05
        grad_step = jax.jit(make_grad_step(model))
        data = SyntheticCodes(model_cfg, num_samples=64, seed=1)
        batches = data.batches(8, seed=i)
        per_epoch = []
        grad_steps = 0
        t0 = time.monotonic()
        deadline = t0 + 120
        try:
            while opt.local_epoch < epochs and time.monotonic() < deadline:
                grads, _ = grad_step(opt.state.params, next(batches))
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(grads)[0])
                grad_steps += 1
                if opt.step(grads, batch_size=8):
                    per_epoch.append(dict(opt.last_timings))
            results[i] = {
                "epochs": opt.local_epoch,
                "grad_steps": grad_steps,
                "wall_s": round(time.monotonic() - t0, 1),
                "rounds": [
                    {"hidden_s": t.get("hidden_s"),
                     "overlapped_grad_steps": t.get("overlapped_steps"),
                     "matchmaking_s": t.get("matchmaking_s"),
                     "allreduce_s": t.get("allreduce_s")}
                    for t in per_epoch],
                "params_digest": float(np.sum(np.abs(np.asarray(
                    jax.tree_util.tree_leaves(opt.state.params)[0],
                    np.float32)))),
            }
        finally:
            opt.shutdown()

    threads = [threading.Thread(target=peer, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)

    assert all(r is not None for r in results), results
    # both peers applied identical averaged updates
    assert abs(results[0]["params_digest"]
               - results[1]["params_digest"]) < 1e-3
    total_overlapped = sum(r0.get("overlapped_grad_steps") or 0
                           for r in results for r0 in r["rounds"])
    total_hidden = sum(r0.get("hidden_s") or 0.0
                       for r in results for r0 in r["rounds"])
    line = json.dumps({
        "metric": "overlapped rounds, 2 peers, "
                  f"{matchmaking_time:.0f}s matchmaking window",
        "peers": results,
        "total_overlapped_grad_steps": total_overlapped,
        "total_hidden_round_s": round(total_hidden, 1),
        "value": total_overlapped,
        "unit": "grad steps executed during swarm rounds",
    })
    print(line, flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "OVERLAP_DEMO.json")
    with open(out, "a") as f:
        f.write(line + "\n")
    for n in nodes:
        n.shutdown()


if __name__ == "__main__":
    main()
