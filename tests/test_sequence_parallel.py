"""Sequence/context parallelism: ring + Ulysses attention over the sp axis.

Correctness strategy: the dense masked oracle (models/attention.py
``dense_zoo_attention``) defines the semantics; every sequence-parallel
program must reproduce it on an 8-virtual-device CPU mesh (conftest.py), and
the full model must produce the same loss/grads with sp>1 as on one device.
The reference has no sequence parallelism to cite (SURVEY.md §5 "Absent");
long-context is a first-class extension here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import (ATTN_AXIAL_COL, ATTN_AXIAL_ROW, ATTN_CONV_LIKE,
                              ATTN_FULL, tiny_model_config)
from dalle_tpu.models.attention import dense_zoo_attention
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.parallel.mesh import make_mesh
from dalle_tpu.parallel.sequence import sp_zoo_attention

TEXT, GRID = 16, 4           # T = 16 + 16 = 32
B, H, D = 4, 4, 8


def _qkv(rng_seed: int = 0):
    rng = np.random.RandomState(rng_seed)
    t = TEXT + GRID * GRID
    shape = (B, t, H, D)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(3))
    return q, k, v


def dense(q, k, v, *stated, **kw):
    """The dense layer as one program (eagerly, a compile an operation)."""
    return jax.jit(lambda q, k, v: dense_zoo_attention(
        q, k, v, *stated, **kw))(q, k, v)


def sp(q, k, v, **kw):
    return jax.jit(lambda q, k, v: sp_zoo_attention(q, k, v, **kw))(q, k, v)


def test_ring_matches_dense_full():
    mesh = make_mesh(dp=2, fsdp=1, tp=1, sp=4)
    q, k, v = _qkv()
    want = dense(q, k, v, ATTN_FULL, TEXT, GRID)
    got = sp(q, k, v, mesh=mesh, mode="ring", attn_type=ATTN_FULL,
             text_len=TEXT, grid=GRID)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_with_tp_axis():
    mesh = make_mesh(dp=1, fsdp=2, tp=2, sp=2)
    q, k, v = _qkv(1)
    want = dense(q, k, v, ATTN_FULL, TEXT, GRID)
    got = sp(q, k, v, mesh=mesh, mode="ring", attn_type=ATTN_FULL,
             text_len=TEXT, grid=GRID)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("attn_type", [ATTN_FULL, ATTN_AXIAL_ROW,
                                       ATTN_AXIAL_COL, ATTN_CONV_LIKE])
def test_ulysses_matches_dense(attn_type):
    mesh = make_mesh(dp=2, fsdp=1, tp=2, sp=2)
    q, k, v = _qkv(2)
    want = dense(q, k, v, attn_type, TEXT, GRID, conv_kernel=3)
    got = sp(q, k, v, mesh=mesh, mode="ulysses", attn_type=attn_type,
             text_len=TEXT, grid=GRID, conv_kernel=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_rejects_sparse_types():
    mesh = make_mesh(dp=2, fsdp=1, tp=1, sp=4)
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="ring"):
        sp_zoo_attention(q, k, v, mesh=mesh, mode="ring",
                         attn_type=ATTN_AXIAL_ROW, text_len=TEXT, grid=GRID)


def test_ring_config_validation():
    with pytest.raises(ValueError, match="ring"):
        tiny_model_config(sequence_parallel="ring",
                          attn_types=(ATTN_AXIAL_ROW,)).validate()
    tiny_model_config(sequence_parallel="ring").validate()  # full-only: ok


def _batch(cfg, seed=0):
    rng = np.random.RandomState(seed)
    text = jnp.asarray(rng.randint(0, cfg.vocab_text,
                                   (B, cfg.text_seq_len)), jnp.int32)
    image = jnp.asarray(rng.randint(0, cfg.vocab_image,
                                    (B, cfg.image_seq_len)), jnp.int32)
    return text, image


def _loss_and_grads(model, params, text, image):
    def loss_fn(p):
        loss, _ = model.apply(p, text, image)
        return loss
    return jax.jit(jax.value_and_grad(loss_fn))(params)


@pytest.mark.parametrize("mode,attn_types,mesh_axes", [
    ("ring", (ATTN_FULL,), dict(dp=2, fsdp=1, tp=1, sp=4)),
    ("ulysses", (ATTN_AXIAL_ROW, ATTN_AXIAL_COL),
     dict(dp=1, fsdp=2, tp=2, sp=2)),
])
def test_model_loss_and_grads_match_single_device(mode, attn_types,
                                                  mesh_axes):
    """Full model: sp>1 shard_map path == single-device reference numerics,
    through remat and the weight-sharing scan."""
    cfg = tiny_model_config(attn_types=attn_types, sequence_parallel=mode,
                            shared_block_cycle=2, depth=4, remat=True)
    mesh = make_mesh(**mesh_axes)
    model_sp = DALLE(cfg, mesh=mesh)
    model_ref = DALLE(cfg.__class__(**{
        **cfg.__dict__, "sequence_parallel": "none"}))
    params = init_params(model_ref, jax.random.PRNGKey(0))
    text, image = _batch(cfg)

    loss_ref, grads_ref = _loss_and_grads(model_ref, params, text, image)
    loss_sp, grads_sp = _loss_and_grads(model_sp, params, text, image)

    np.testing.assert_allclose(float(loss_sp), float(loss_ref),
                               rtol=1e-5, atol=1e-5)
    flat_ref = jax.tree.leaves(grads_ref)
    flat_sp = jax.tree.leaves(grads_sp)
    for a, b in zip(flat_sp, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("mode,attn_types", [
    ("ring", (ATTN_FULL,)),
    ("ulysses", (ATTN_AXIAL_ROW, ATTN_AXIAL_COL)),
])
def test_grad_step_on_a_dp_mesh_matches_single_device(mode, attn_types):
    """With dp > 1 the gradient step runs the model inside a ``shard_map``
    manual over ``dp`` (training/steps.py): the sp programs nest in it,
    bind the other axes only, and take their ring position as an operand
    (``axis_index`` does not lower there). One microbatch, so the
    attention sits in no accumulation scan."""
    from dalle_tpu.training.steps import make_grad_step
    cfg = tiny_model_config(attn_types=attn_types, sequence_parallel=mode,
                            shared_block_cycle=2, depth=4, remat=True)
    mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
    model_ref = DALLE(cfg.__class__(**{
        **cfg.__dict__, "sequence_parallel": "none"}))
    params = init_params(model_ref, jax.random.PRNGKey(0))
    text, image = _batch(cfg)
    batch = {"text": text, "image": image}

    grads_ref, aux_ref = jax.jit(make_grad_step(model_ref))(params, batch)
    grads_sp, aux_sp = jax.jit(make_grad_step(DALLE(cfg, mesh=mesh)))(
        params, batch)
    np.testing.assert_allclose(float(aux_sp["loss"]), float(aux_ref["loss"]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_sp), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
