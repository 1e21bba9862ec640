"""Multi-host slices: one swarm peer per pod slice (parallel/multihost.py).

The north-star deployment: a whole pod slice presents as ONE volunteer —
process 0 speaks the swarm protocol, followers receive decisions/averages
via broadcasts (SURVEY.md §5 comm backend; the reference's analogue is the
single host process of a TPU-VM talking to hivemind while 8 cores
all-reduce locally, run_trainer_tpu.py:78-91).

The integration test runs TWO real JAX processes joined through
``jax.distributed.initialize`` on the CPU backend and checks both end a
swarm epoch with byte-identical parameters.
"""

import json
import os
import re
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent


def _cpu_multiprocess_collectives() -> str:
    """The configured CPU collectives implementation ("none" when
    multiprocess CPU computations are unsupported).

    Every child below pins ``JAX_PLATFORMS=cpu``, so what decides
    whether these tests CAN pass is whether the CPU client gets a
    collectives backend (gloo/mpi). jaxlib ships gloo, but the
    ``jax_cpu_collectives_implementation`` flag defaults to "none" — and
    with "none" the very first cross-process computation raises
    ``XlaRuntimeError: Multiprocess computations aren't implemented on
    the CPU backend``, which makes all three subprocess tests
    guaranteed failures (each burning its full matchmaking/averaging
    timeout). Children inherit our environment, so reading the parent's
    flag is faithful: export ``JAX_CPU_COLLECTIVES_IMPLEMENTATION=gloo``
    (or ``jax.config.update`` in a conftest) and the skip lifts.
    """
    try:
        from jax._src import xla_bridge
        return xla_bridge.CPU_COLLECTIVES_IMPLEMENTATION.value or "none"
    except Exception:
        return "none"


pytestmark = pytest.mark.skipif(
    _cpu_multiprocess_collectives() == "none",
    reason="Multiprocess computations aren't implemented on the CPU "
           "backend: jax_cpu_collectives_implementation is 'none' (set "
           "JAX_CPU_COLLECTIVES_IMPLEMENTATION=gloo to run these)")

_CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1]); port = sys.argv[2]; dht_port = sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
import jax.numpy as jnp
import numpy as np
import optax

from dalle_tpu.config import CollabConfig
from dalle_tpu.parallel.multihost import SliceRole
from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
from dalle_tpu.training.steps import TrainState, make_apply_step

role = SliceRole()
assert role.n_processes == 2
dht = None
if role.swarm_enabled:
    from dalle_tpu.swarm.dht import DHT
    from dalle_tpu.swarm.identity import Identity
    dht = DHT(host="127.0.0.1", port=int(dht_port),
              identity=Identity.generate())

cfg = CollabConfig(run_id="mh", target_batch_size=16,
                   matchmaking_time=1.0, allreduce_timeout=10.0,
                   averaging_timeout=20.0, average_state_every=0,
                   grad_compression="none")
tx = optax.sgd(0.1)
params = {"w": jnp.ones((8, 4), jnp.float32)}
state = TrainState.create(params, tx)
opt = CollaborativeOptimizer(dht, cfg, state, jax.jit(make_apply_step(tx)),
                             serve_state=False, matchmaking_min_group=1,
                             role=role)
if role.swarm_enabled:
    opt.tracker.min_refresh_period = 0.05

grads = {"w": jnp.full((8, 4), 2.0, jnp.float32)}
steps = 0
while opt.local_epoch < 1 and steps < 50:
    opt.step(grads, batch_size=8)
    steps += 1

w = np.asarray(opt.state.params["w"])
print(json.dumps({"pid": pid, "epoch": opt.local_epoch,
                  "steps": steps,
                  "w0": float(w.flat[0]),
                  "digest": __import__("hashlib").sha256(
                      w.tobytes()).hexdigest()}))
opt.shutdown()  # drain any background round BEFORE the native node dies
if dht is not None:
    dht.shutdown()
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_slice_applies_identical_updates():
    env = dict(os.environ)
    # one cpu device per process
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    port, dht_port = _free_port(), _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(pid), str(port),
             str(dht_port)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise AssertionError(
            "multihost children hung:\n" +
            "\n".join(o[-2000:] for o in outs))

    results = []
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
        results.append(json.loads(line))
    by_pid = {r["pid"]: r for r in results}
    assert by_pid[0]["epoch"] == by_pid[1]["epoch"] == 1
    # both processes applied the identical update: w = 1 - 0.1*2 = 0.8
    assert abs(by_pid[0]["w0"] - 0.8) < 1e-5
    assert by_pid[0]["digest"] == by_pid[1]["digest"]
    # followers and coordinator ran the same number of lockstep steps
    assert by_pid[0]["steps"] == by_pid[1]["steps"]


_SLICE_CHILD = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1]); port = sys.argv[2]; dht_port = sys.argv[3]
compression = sys.argv[4]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=pid)
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from dalle_tpu.config import CollabConfig
from dalle_tpu.parallel.multihost import SliceRole
from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
from dalle_tpu.training.steps import TrainState, make_apply_step

role = SliceRole()
dht = None
if role.swarm_enabled:
    from dalle_tpu.swarm.dht import DHT
    from dalle_tpu.swarm.identity import Identity
    dht = DHT(host="127.0.0.1", port=int(dht_port),
              identity=Identity.generate())

cfg = CollabConfig(run_id="mhs", target_batch_size=32,
                   matchmaking_time=3.0, allreduce_timeout=15.0,
                   averaging_timeout=30.0, average_state_every=0,
                   grad_compression=compression, powersgd_rank=2,
                   encrypt_data_plane=False)
# state sharded ACROSS the two processes (1 CPU device each) — the
# ADVICE-r2 crash scenario: np.asarray on such arrays raises
mesh = jax.make_mesh((2,), ("fsdp",))
shard = NamedSharding(mesh, P("fsdp"))
rep = NamedSharding(mesh, P())
tx = optax.sgd(0.1)
params = {"w": jax.device_put(np.ones((64, 32), np.float32), shard),
          "b": jax.device_put(np.zeros((32,), np.float32), rep)}
state = TrainState.create(params, tx)
opt = CollaborativeOptimizer(dht, cfg, state, jax.jit(make_apply_step(tx)),
                             serve_state=False, matchmaking_min_group=2,
                             role=role)
if role.swarm_enabled:
    opt.tracker.min_refresh_period = 0.05

grads = {"w": jax.device_put(np.full((64, 32), 2.0, np.float32), shard),
         "b": jax.device_put(np.full((32,), 1.0, np.float32), rep)}
steps = 0
deadline = time.monotonic() + 120
while opt.local_epoch < 1 and time.monotonic() < deadline:
    opt.step(grads, batch_size=8)
    steps += 1
from dalle_tpu.parallel.multihost import host_global
w, b = host_global([opt.state.params["w"], opt.state.params["b"]])
print(json.dumps({"pid": pid, "epoch": opt.local_epoch, "steps": steps,
                  "w0": float(w.flat[0]), "b0": float(b.flat[0]),
                  "digest": __import__("hashlib").sha256(
                      w.tobytes() + b.tobytes()).hexdigest()}))
opt.shutdown()  # drain any background round BEFORE the native node dies
if dht is not None:
    dht.shutdown()
"""

_PLAIN_PEER_CHILD = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
dht_port = sys.argv[1]; compression = sys.argv[2]
import jax.numpy as jnp
import numpy as np
import optax

from dalle_tpu.config import CollabConfig
from dalle_tpu.swarm.dht import DHT
from dalle_tpu.swarm.identity import Identity
from dalle_tpu.swarm.optimizer import CollaborativeOptimizer
from dalle_tpu.training.steps import TrainState, make_apply_step

dht = DHT(host="127.0.0.1", port=0, identity=Identity.generate())
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    if dht.bootstrap(f"127.0.0.1:{dht_port}"):
        break
    time.sleep(0.25)
else:
    raise SystemExit("could not bootstrap to the slice coordinator")

cfg = CollabConfig(run_id="mhs", target_batch_size=32,
                   matchmaking_time=3.0, allreduce_timeout=15.0,
                   averaging_timeout=30.0, average_state_every=0,
                   grad_compression=compression, powersgd_rank=2,
                   encrypt_data_plane=False)
tx = optax.sgd(0.1)
params = {"w": jnp.ones((64, 32), jnp.float32),
          "b": jnp.zeros((32,), jnp.float32)}
state = TrainState.create(params, tx)
opt = CollaborativeOptimizer(dht, cfg, state, jax.jit(make_apply_step(tx)),
                             serve_state=False, matchmaking_min_group=2)
opt.tracker.min_refresh_period = 0.05

grads = {"w": jnp.full((64, 32), 4.0, jnp.float32),
         "b": jnp.full((32,), 3.0, jnp.float32)}
steps = 0
deadline = time.monotonic() + 120
while opt.local_epoch < 1 and time.monotonic() < deadline:
    opt.step(grads, batch_size=8)
    steps += 1
w = np.asarray(opt.state.params["w"])
b = np.asarray(opt.state.params["b"])
print(json.dumps({"pid": "peer", "epoch": opt.local_epoch, "steps": steps,
                  "w0": float(w.flat[0]), "b0": float(b.flat[0])}))
# overlapped rounds (delay_optimizer_step) may still be on the wire:
# the optimizer MUST shut down before the native DHT node is destroyed
# (task.shutdown's ordering) or the round thread touches freed memory
opt.shutdown()
dht.shutdown()
"""


def _run_sharded_slice_with_peer(compression: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", "")).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    port, dht_port = _free_port(), _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _SLICE_CHILD, str(pid), str(port),
             str(dht_port), compression],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)
    ]
    procs.append(subprocess.Popen(
        [sys.executable, "-c", _PLAIN_PEER_CHILD, str(dht_port),
         compression],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise AssertionError(
            "sharded-slice children hung:\n" +
            "\n".join(o[-2000:] for o in outs))

    results = {}
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
        r = json.loads(line)
        results[r["pid"]] = r
    # everyone finished the epoch
    assert results[0]["epoch"] == results[1]["epoch"] == 1
    assert results["peer"]["epoch"] == 1
    # the sample-weighted mean of the two peers' constant grads lies
    # strictly between them (w in [2,4], b in [1,3]; the free-running
    # plain peer usually accumulates more samples than the lockstep
    # slice, so the exact point depends on timing), and w/b must tell a
    # CONSISTENT story: b's per-sample grad is exactly w's minus 1
    for r in (results[0], results[1], results["peer"]):
        w_avg = (1.0 - r["w0"]) * 10.0
        b_avg = -r["b0"] * 10.0
        assert 2.0 - 1e-3 <= w_avg <= 4.0 + 1e-3, r
        assert abs(b_avg - (w_avg - 1.0)) < 5e-3, r
    # every participant applied the same averaged gradients
    assert abs(results[0]["w0"] - results["peer"]["w0"]) < 1e-4
    # the slice's two processes are byte-identical
    assert results[0]["digest"] == results[1]["digest"]


def test_sharded_slice_cotrains_with_plain_peer_powersgd():
    """ADVICE r2 (medium): a slice whose state/grads are sharded ACROSS
    processes must survive the global step — the PowerSGD device phases
    run as SPMD collectives on every process, factors are all-gathered
    for the wire, and the completeness flag is broadcast."""
    _run_sharded_slice_with_peer("power_sgd")


def test_sharded_slice_cotrains_with_plain_peer_allreduce():
    """Same scenario through the plain all-reduce path: the sharded
    gradient pull is a lockstep all-gather and the averaged result is
    broadcast to followers."""
    _run_sharded_slice_with_peer("none")
