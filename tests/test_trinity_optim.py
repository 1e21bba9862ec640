"""LAMB on the leaves ``AfmoeLMConfig`` adds: the router's bias, which no
gradient reaches, stays what it is, bit for bit; each routed expert keeps
a trust ratio of its own; the shared expert's and the dense block's leaves
are ordinary ones."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dalle_tpu.config import AfmoeLMConfig
from dalle_tpu.models import sparse_lm
from dalle_tpu.optim import lamb, lamb8bit
from dalle_tpu.optim.lamb import default_stacked_mask

TINY = dict(hidden_size=32, num_hidden_layers=2, num_heads=2, num_kv_heads=1,
            head_dim=16, expert_width=16, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=0, vocab_size=64, window=8,
            layer_kinds=("window_rope", "full_nope"), text_seq_len=8,
            image_grid=2, vocab_text=32, vocab_image=32, dtype="float32",
            head_chunk=8, dense_width=48)


def _tx(bits, experts):
    kw = dict(learning_rate=0.1, max_grad_norm=None, stacked_reps=0,
              stacked_experts=experts)
    return lamb(**kw) if bits == 32 else lamb8bit(min_8bit_size=1 << 30,
                                                  **kw)


@pytest.fixture(scope="module")
def stepped():
    """The tiny model's parameters and its real gradients."""
    cfg = AfmoeLMConfig(**TINY)
    model = sparse_lm.build(cfg)
    params = sparse_lm.init_params(model, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    text = jnp.asarray(rng.integers(2, 32, (2, 8)), jnp.int32)
    image = jnp.asarray(rng.integers(0, 32, (2, 4)), jnp.int32)
    grads = jax.jit(jax.grad(
        lambda p: model.apply(p, text, image)[0]))(params)
    return cfg, params, grads


def test_the_mask_counts_the_routed_experts_and_no_other_leaf(stepped):
    cfg, params, _ = stepped
    stacking = cfg.optimizer_stacking()
    assert stacking == {"stacked_reps": 0, "stacked_experts": 4}
    mask = default_stacked_mask(params, **{
        "reps": stacking["stacked_reps"],
        "experts": stacking["stacked_experts"]})["params"]
    ff = mask["layer_1"]["ff"]
    assert ff["experts"] == {"gate": 1, "up": 1, "down": 1}
    assert ff["router"] == 0 and ff["router_bias"] == 0
    assert set(jax.tree.leaves(ff["shared"])) == {0}
    assert set(jax.tree.leaves(mask["layer_0"]["ff"]["dense"])) == {0}
    assert set(jax.tree.leaves(mask["layer_1"]["attn"])) == {0}


@pytest.mark.parametrize("bits", [32, 8])
def test_one_apply_step(bits, stepped):
    cfg, params, grads = stepped
    bias = lambda tree: tree["params"]["layer_1"]["ff"]["router_bias"]
    assert not np.asarray(bias(grads)).any()       # exactly zero
    tx = _tx(bits, cfg.experts_held)
    updates, _ = tx.update(grads, tx.init(params), params)
    after = optax.apply_updates(params, updates)
    # the bias: zero gradient on a zero value moves nothing, and divides
    # nothing by zero
    assert np.isfinite(np.asarray(bias(updates))).all()
    np.testing.assert_array_equal(bias(after), bias(params))
    assert np.asarray(bias(after)).tobytes() == bytes(8 * 4)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in jax.tree.leaves(after))
    # every routed expert alone: the stacked leaf's update is each slice's
    plain = _tx(bits, 0)
    gate = lambda tree: tree["params"]["layer_1"]["ff"]["experts"]["gate"]
    for e in range(cfg.experts_held):
        pick = lambda tree: jax.tree.map(
            lambda a: a[e] if a.ndim == 3 else a, tree)
        alone = gate(plain.update(pick(grads), plain.init(pick(params)),
                                  pick(params))[0])
        np.testing.assert_allclose(gate(updates)[e], alone, rtol=2e-5,
                                   atol=1e-9)
    # the shared expert's, the dense block's and the gate's leaves are
    # ordinary: one ratio a tensor, with or without the expert axis named
    ordinary, _ = plain.update(grads, plain.init(params), params)
    for path in (("layer_1", "ff", "shared", "up", "kernel"),
                 ("layer_0", "ff", "dense", "down", "kernel"),
                 ("layer_1", "attn", "gate", "kernel"),
                 ("layer_1", "attn", "q_norm")):
        a, b = updates["params"], ordinary["params"]
        for key in path:
            a, b = a[key], b[key]
        np.testing.assert_array_equal(a, b)
        assert float(jnp.abs(a).max()) > 0
