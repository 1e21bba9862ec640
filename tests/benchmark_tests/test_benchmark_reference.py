"""The ``dalle`` yardstick's plain reference against the system at the tiny
preset's widths on the CPU, in the flagship's layer pattern (shared axial
blocks in a scan with an overhanging iteration, a final conv block, rotary,
tied head)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.config import tiny_model_config
from dalle_tpu.models.dalle import DALLE, init_params

reference = Manifest().yardstick("dalle")

PATTERN = dict(shared_block_cycle=4, final_conv_block=True, depth=10,
               scan_unroll=2, conv_kernel=3,
               attn_types=("axial_row", "axial_col", "axial_row",
                           "axial_row"))


def _setup(**over):
    cfg = tiny_model_config(**dict(PATTERN, **over))
    model = DALLE(cfg)
    params = init_params(model, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    text = jnp.asarray(rng.integers(2, cfg.vocab_text,
                                    (2, cfg.text_seq_len)), jnp.int32)
    image = jnp.asarray(rng.integers(0, cfg.vocab_image,
                                     (2, cfg.image_seq_len)), jnp.int32)
    group = {k: list(v) if isinstance(v, tuple) else v
             for k, v in dataclasses.asdict(cfg).items()}
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.apply(p, text, image), has_aux=True)(params)
    return group, params, text, image, float(loss), grads


def _worst(grads, ref_grads):
    return max(float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
               for g, r in zip(jax.tree.leaves(grads),
                               jax.tree.leaves(ref_grads)))


@pytest.mark.parametrize("checkpoint_blocks", [False, True])
def test_reference_matches_system_in_float32(checkpoint_blocks):
    group, params, text, image, loss, grads = _setup()
    ref_loss, ref_grads = reference.loss_and_grads(
        params, text, image, group, checkpoint_blocks=checkpoint_blocks)
    # float32 on both sides: differences are summation order only
    assert abs(loss - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    assert _worst(grads, ref_grads) <= 1e-4


def test_reference_tells_a_wrong_mask_apart():
    group, params, text, image, loss, grads = _setup()
    wrong = dict(group, attn_types=["axial_col"] * 4)
    ref_loss, ref_grads = reference.loss_and_grads(params, text, image,
                                                   wrong)
    assert _worst(grads, ref_grads) > 0.05


def test_bfloat16_system_is_near_but_not_at_float32():
    """What the on-chip tolerance rests on: bf16 activations land orders
    of magnitude further from the reference than float32 does, and well
    inside the configuration files' bounds."""
    group, params, text, image, loss, grads = _setup(dtype="bfloat16")
    ref_loss, ref_grads = reference.loss_and_grads(params, text, image,
                                                   group)
    rel = abs(loss - float(ref_loss)) / abs(float(ref_loss))
    worst = _worst(grads, ref_grads)
    assert 1e-6 < rel < 3e-3
    assert 1e-3 < worst < 0.15


def test_schedule_follows_the_flagship_pattern():
    sched = reference.layer_schedule(
        {"depth": 64, "final_conv_block": True, "shared_block_cycle": 4,
         "attn_types": ["axial_row", "axial_col", "axial_row", "axial_row"]})
    assert len(sched) == 64 and sched[-1] == (-1, "conv_like")
    assert sched[:5] == [(0, "axial_row"), (1, "axial_col"),
                         (2, "axial_row"), (3, "axial_row"),
                         (0, "axial_row")]
    assert sched[62] == (62 % 4, "axial_row")
