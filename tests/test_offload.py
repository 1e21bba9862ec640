"""Host-offloaded optimizer state (training/offload.py).

Parity target: the reference's ``OffloadOptimizer``
(``lib/training/offload.py:10-93``) must be numerically invisible — the
offloaded apply produces exactly the same parameters as the on-device
apply, with the optimizer state resident on the host CPU device.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dalle_init import init_params
from dalle_tpu.config import OptimizerConfig, tiny_model_config
from dalle_tpu.data.synthetic import SyntheticCodes
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.optim import make_optimizer
from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
from dalle_tpu.parallel.sharding import shard_train_state
from dalle_tpu.training.offload import (host_device,
                                        make_offloaded_apply_step,
                                        offload_train_state)
from dalle_tpu.training.steps import (TrainState, make_apply_step,
                                      make_grad_step)


def _setup(opt_cfg, mesh):
    cfg = tiny_model_config()
    model = DALLE(cfg)
    params = init_params(model, jax.random.PRNGKey(0))
    tx = make_optimizer(opt_cfg)
    state = TrainState.create(params, tx)
    data = SyntheticCodes(cfg, num_samples=16, seed=0)
    batch = jax.device_put(next(data.batches(8, seed=0)),
                           batch_sharding(mesh))
    grads, _ = jax.jit(make_grad_step(model))(params, batch)
    return tx, state, grads


def test_offloaded_apply_matches_on_device():
    mesh = make_mesh(dp=2, fsdp=2, tp=2, sp=1)
    for opt_cfg in (OptimizerConfig(warmup_steps=2, total_steps=10,
                                    state_bits=32),
                    OptimizerConfig(warmup_steps=2, total_steps=10,
                                    state_bits=8, min_8bit_size=16)):
        tx, state, grads = _setup(opt_cfg, mesh)

        on_dev = shard_train_state(mesh, state)
        on_dev = jax.jit(make_apply_step(tx))(on_dev, grads)

        off = offload_train_state(mesh, state)
        off = make_offloaded_apply_step(tx, mesh)(off, grads)

        for a, b in zip(jax.tree.leaves(off.params),
                        jax.tree.leaves(on_dev.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        assert int(off.step) == int(on_dev.step) == 1


def test_offloaded_state_lives_on_host_and_params_on_mesh():
    mesh = make_mesh(dp=2, fsdp=2, tp=2, sp=1)
    tx, state, grads = _setup(
        OptimizerConfig(warmup_steps=2, total_steps=10, state_bits=32), mesh)
    off = offload_train_state(mesh, state)
    cpu = host_device()

    def devices_of(x):
        return {getattr(s, "device", s) for s in (
            x.sharding.device_set if hasattr(x.sharding, "device_set")
            else [x.devices()])}

    for leaf in jax.tree.leaves(off.opt_state):
        assert leaf.sharding.device_set == {cpu}, leaf
    # params ride the mesh, not the host
    some_param = jax.tree.leaves(off.params)[0]
    assert cpu not in some_param.sharding.device_set or len(
        some_param.sharding.device_set) > 1

    # state remains host-resident across applies
    off = make_offloaded_apply_step(tx, mesh)(off, grads)
    for leaf in jax.tree.leaves(off.opt_state):
        assert leaf.sharding.device_set == {cpu}

    # and a second apply works on the donated/updated state
    off2 = make_offloaded_apply_step(tx, mesh)(off, grads)
    assert int(off2.step) == 2


def test_task_wires_offload():
    from dalle_tpu.config import (CollabConfig, PeerConfig, TrainerConfig)
    from dalle_tpu.task import TrainingTask

    task = TrainingTask(
        model=tiny_model_config(),
        optimizer=OptimizerConfig(warmup_steps=2, total_steps=10,
                                  offload=True, state_bits=32),
        trainer=TrainerConfig(dp=2, fsdp=2, tp=2, per_device_batch=1),
        collab=CollabConfig(),
        peer=PeerConfig())
    cpu = host_device()
    state = task.train_state
    for leaf in jax.tree.leaves(state.opt_state):
        assert leaf.sharding.device_set == {cpu}
    grads, _ = task.grad_step(state.params, next(task.batches()))
    new_state = task.apply_step(state, grads)
    assert int(new_state.step) == 1
    for leaf in jax.tree.leaves(new_state.opt_state):
        assert leaf.sharding.device_set == {cpu}
