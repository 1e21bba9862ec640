"""8-peer swarm scale bench on loopback (VERDICT r2 next #4).

Runs N in-process peers — full, plain-client and relay-attached-client
mix — through several collaborative epochs with a mid-run kill and a
mid-run join, and prints the per-phase epoch timing table that
SWARM_SCALE.md records. Run:

    JAX_PLATFORMS=cpu python scripts/swarm_scale_bench.py [N]
"""

import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dalle_tpu.config import CollabConfig  # noqa: E402
from dalle_tpu.swarm import DHT, Identity  # noqa: E402
from dalle_tpu.swarm.optimizer import CollaborativeOptimizer  # noqa: E402
from dalle_tpu.training.steps import TrainState, make_apply_step  # noqa: E402


def build_swarm(n_full: int, n_client: int, n_relay: int, cfg: CollabConfig):
    boot = DHT(rpc_timeout=2.0, identity=Identity.generate())
    nodes, kinds = [boot], ["full(boot/relay)"]
    for _ in range(n_full - 1):
        nodes.append(DHT(rpc_timeout=2.0, identity=Identity.generate(),
                         initial_peers=[boot.visible_address]))
        kinds.append("full")
    for _ in range(n_client):
        nodes.append(DHT(client_mode=True, rpc_timeout=2.0,
                         identity=Identity.generate(),
                         initial_peers=[boot.visible_address]))
        kinds.append("client")
    for _ in range(n_relay):
        d = DHT(client_mode=True, rpc_timeout=2.0,
                identity=Identity.generate(),
                initial_peers=[boot.visible_address])
        assert d.attach_relay(boot.visible_address)
        nodes.append(d)
        kinds.append("client+relay")

    opts = []
    for d, kind in zip(nodes, kinds):
        params = {"w": jnp.ones((256, 64)) * 0.5, "b": jnp.zeros((64,))}
        tx = optax.sgd(0.05)
        opt = CollaborativeOptimizer(
            d, cfg, TrainState.create(params, tx),
            jax.jit(make_apply_step(tx)),
            client_mode="client" in kind and "relay" not in kind,
            serve_state="full" in kind)
        opt.tracker.min_refresh_period = 0.05
        opts.append(opt)
    return nodes, opts, kinds


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    n_full, n_client, n_relay = n - 3, 2, 1
    cfg = CollabConfig(run_id="scale", target_batch_size=64 * n,
                       matchmaking_time=3.0, allreduce_timeout=15.0,
                       averaging_timeout=30.0, average_state_every=0,
                       grad_compression="size_adaptive")
    nodes, opts, kinds = build_swarm(n_full, n_client, n_relay, cfg)
    timings = {i: [] for i in range(len(opts))}
    target_epochs = int(os.environ.get("SWARM_SCALE_EPOCHS", "4"))
    stop = threading.Event()

    def run_peer(i):
        opt = opts[i]
        grads = {"w": jnp.full((256, 64), float(i + 1)),
                 "b": jnp.full((64,), 1.0)}
        while (opt.local_epoch < target_epochs and not stop.is_set()):
            if i == 1 and opt.local_epoch >= 2:
                return  # peer 1 dies after epoch 2 (mid-run kill)
            stepped = opt.step(grads, batch_size=8)
            if stepped and opt.last_timings:
                timings[i].append(
                    {"epoch": opt.local_epoch, **opt.last_timings})
            time.sleep(0.02)

    threads = [threading.Thread(target=run_peer, args=(i,))
               for i in range(len(opts))]
    t0 = time.monotonic()
    for t in threads:
        t.start()

    # mid-run join: a fresh full peer bootstraps state from the swarm
    time.sleep(8.0)
    joiner = DHT(rpc_timeout=2.0, identity=Identity.generate(),
                 initial_peers=[nodes[0].visible_address])
    params = {"w": jnp.zeros((256, 64)), "b": jnp.zeros((64,))}
    tx = optax.sgd(0.05)
    jopt = CollaborativeOptimizer(joiner, cfg,
                                  TrainState.create(params, tx),
                                  jax.jit(make_apply_step(tx)))
    jopt.tracker.min_refresh_period = 0.05
    joined = jopt.load_state_from_peers()
    kinds.append("full(joiner)")
    opts.append(jopt)
    timings[len(opts) - 1] = []
    jt = threading.Thread(target=run_peer, args=(len(opts) - 1,))
    jt.start()
    threads.append(jt)

    deadline = time.monotonic() + float(
        os.environ.get("SWARM_SCALE_DEADLINE", "180"))
    for t in threads:
        t.join(max(1.0, deadline - time.monotonic()))
    stop.set()
    wall = time.monotonic() - t0

    print(f"\nswarm scale: {n}+1 peers ({n_full} full, {n_client} client, "
          f"{n_relay} relay-attached), kill@2, join@8s, wall {wall:.1f}s, "
          f"joiner bootstrap={'ok' if joined else 'FAILED'}")
    print(f"{'peer':>4} {'kind':<16} {'epochs':>6} {'match_s':>8} "
          f"{'reduce_s':>9} {'apply_s':>8} {'pull_s':>7}")
    for i, kind in enumerate(kinds):
        rows = timings.get(i, [])
        if not rows:
            print(f"{i:>4} {kind:<16} {opts[i].local_epoch:>6} "
                  f"{'-':>8} {'-':>9} {'-':>8} {'-':>7}")
            continue
        med = lambda k: float(np.median([r.get(k, 0.0) for r in rows]))  # noqa
        print(f"{i:>4} {kind:<16} {opts[i].local_epoch:>6} "
              f"{med('matchmaking_s'):>8.2f} {med('allreduce_s'):>9.2f} "
              f"{med('apply_s'):>8.3f} {med('grad_pull_s'):>7.3f}")

    finals = [np.asarray(o.state.params["w"]).mean() for o in opts
              if o.local_epoch >= target_epochs]
    print(f"final-mean(w) across finished peers: "
          f"{[round(float(x), 4) for x in finals[:4]]} ... "
          f"spread={float(np.ptp(finals)):.2e}" if finals else "none finished")

    ok = sum(1 for o in opts if o.local_epoch >= target_epochs)
    print(f"{ok}/{len(opts)} peers reached epoch {target_epochs}")
    for o in opts:
        o.shutdown()
    for d in nodes + [joiner]:
        d.shutdown()
    return 0 if ok >= len(opts) - 2 else 1  # the killed peer + slack


if __name__ == "__main__":
    sys.exit(main())
