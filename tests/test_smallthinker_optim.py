"""LAMB on an expert layer's stacked leaves: one trust ratio per expert
(per layer: every layer's leaf is its own), from the configuration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import OptimizerConfig, smallthinker21b_model_config
from dalle_tpu.optim import lamb, lamb8bit, make_optimizer
from dalle_tpu.optim.lamb import default_stacked_mask


def _params(experts=4):
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    scale = jnp.asarray([0.1, 1.0, 3.0, 10.0])[:, None, None]
    return {"params": {"layer_0": {
        "ff": {"experts": {"gate": jax.random.normal(k[0], (experts, 8, 6))
                           * scale,
                           "down": jax.random.normal(k[1], (experts, 6, 8))},
               "router": jax.random.normal(k[2], (8, 16))},
        "attn": {"q": {"kernel": jax.random.normal(k[3], (8, 8))}},
        "attn_norm": jnp.ones((8,))}}}


def test_the_mask_counts_the_expert_axis_from_the_configuration():
    params = _params()
    cfg = smallthinker21b_model_config(experts_held=4)
    assert cfg.optimizer_stacking() == {"stacked_reps": 0,
                                        "stacked_experts": 4}
    mask = default_stacked_mask(params, 0, 4)["params"]["layer_0"]
    assert mask["ff"]["experts"] == {"gate": 1, "down": 1}
    assert mask["ff"]["router"] == 0 and mask["attn"]["q"]["kernel"] == 0
    # a leading axis of another size, or a model with no experts: none
    none = default_stacked_mask(params, 0, 8)["params"]["layer_0"]
    assert none["ff"]["experts"] == {"gate": 0, "down": 0}
    assert not any(jax.tree.leaves(default_stacked_mask(params, 0, 0)))


def _tx(bits, experts):
    kw = dict(learning_rate=1.0, max_grad_norm=None, stacked_reps=0,
              stacked_experts=experts)
    return lamb(**kw) if bits == 32 else lamb8bit(min_8bit_size=1 << 30,
                                                  **kw)


@pytest.mark.parametrize("bits", [32, 8])
def test_the_update_of_stacked_experts_is_each_expert_alone(bits):
    """Updating the stacked leaf equals updating every expert's slice as a
    tensor of its own: the trust ratio is per expert."""
    params = _params()
    grads = jax.tree.map(lambda p: jnp.cos(p * 3.0), params)
    gate = lambda tree: tree["params"]["layer_0"]["ff"]["experts"]["gate"]
    tx = _tx(bits, 4)
    got = gate(tx.update(grads, tx.init(params), params)[0])
    plain = _tx(bits, 0)
    for e in range(4):
        pick = lambda tree: jax.tree.map(
            lambda a: a[e] if a.ndim == 3 else a, tree)
        alone = gate(plain.update(pick(grads), plain.init(pick(params)),
                                  pick(params))[0])
        np.testing.assert_allclose(got[e], alone, rtol=2e-5)
    # one ratio for the whole leaf is a different update
    shared = gate(plain.update(grads, plain.init(params), params)[0])
    assert float(jnp.abs(shared - got).max()) > 1e-3 * float(
        jnp.abs(got).max())


def test_the_task_threads_the_expert_axis_into_the_optimizer():
    """``OptimizerConfig.stacked_experts`` comes from the model's
    configuration (``optimizer_stacking``) unless the user set it."""
    cfg = OptimizerConfig()
    assert cfg.stacked_experts is None and cfg.stacked_reps is None
    tx = make_optimizer(OptimizerConfig(state_bits=32, stacked_reps=0,
                                        stacked_experts=4))
    params = _params()
    assert jax.tree.structure(tx.init(params).mu) == jax.tree.structure(
        params)
