"""``JoyAILMConfig`` (preset ``joyaiflash``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: loss and every gradient leaf
against the plain reference of its yardstick under both lowerings; the
latent kernels against the dense lowering of the same equations; the
rotary's interleaved pairs against a complex multiply; the preset trains
through the peer's normal path and the entry points that decode refuse it.
(What it shares with ``trinitymini`` is parametrised in
tests/test_trinity_model.py: a mechanism left out is told, the shares add
up to the uncut layer.)"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.cli import run_aux_peer, run_inference, run_server, run_trainer
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, SparseLMConfig,
                              joyaiflash_model_config)
from dalle_tpu.models import attention, family, sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels

Y = Manifest().yardstick("joyai")

# a dense layer, two expert layers and the prediction module; a sequence
# (40: no other test file's) of two fields, half of the router's experts
TINY = dict(hidden_size=64, num_hidden_layers=3, num_heads=4, num_kv_heads=4,
            expert_width=32, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, text_seq_len=24,
            image_grid=4, vocab_text=48, vocab_image=48, dtype="float32",
            head_chunk=16, dense_width=96, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
# the widths the kernels take (interpreted): 128 + 64 | 128
KERNEL_WIDTHS = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                     v_head_dim=128, hidden_size=128, expert_width=128,
                     dense_width=128)


def as_file(cfg):
    return json.loads(json.dumps(dataclasses.asdict(cfg)))


def _batch(cfg, seed=0, n=2):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(2, cfg.vocab_text,
                                     (n, cfg.text_seq_len)), jnp.int32),
            jnp.asarray(rng.integers(0, cfg.vocab_image,
                                     (n, cfg.image_seq_len)), jnp.int32))


def rel_l2(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def _params(cfg, seed=1):
    """Seeded weights with every vector leaf (norm scales, the router's
    bias) moved off its initial ones and zeros, so that each counts."""
    params = sparse_lm.init_params(sparse_lm.build(cfg),
                                   jax.random.PRNGKey(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


@pytest.mark.parametrize("with_kernels", [False, True])
def test_loss_and_every_gradient_leaf_against_the_yardstick(
        with_kernels, monkeypatch, lowering_record):
    """The whole tiny model, the prediction module's loss in it; with
    ``with_kernels`` the latent attention, the grouped products and the
    token-major sums run their Pallas kernels, interpreted. Limits: f32 on
    both sides, the reference at the highest matmul precision; the program
    and the reference order their sums differently (blockwise softmax,
    streamed head, sorted experts), which the parent's test of the other
    configuration reads at the same 2e-6 / 2e-5."""
    cfg = JoyAILMConfig(**dict(TINY, **(KERNEL_WIDTHS if with_kernels
                                        else {})))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", with_kernels)
    params = _params(cfg)
    text, image = _batch(cfg)
    model = sparse_lm.build(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, image), has_aux=True))(params)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image, as_file(cfg))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5, jax.tree_util.keystr(path)
    # the loss is the sum of its two parts, which ride beside it
    (_, (main, mtp)) = jax.jit(lambda p: Y.loss_fn(
        p, text, image, as_file(cfg)))(params)
    assert float(aux["loss_main"]) == pytest.approx(float(main), rel=2e-6)
    assert float(aux["loss_mtp"]) == pytest.approx(float(mtp), rel=2e-6)
    assert float(loss) == pytest.approx(
        float(main) + cfg.mtp_loss_weight * float(mtp), rel=1e-6)
    tree = params["params"]
    assert set(tree) == {"token_emb", "lm_head", "final_norm", "mtp",
                         "layer_0", "layer_1", "layer_2"}
    assert set(tree["layer_1"]) == {"attn", "attn_norm", "ff", "ff_norm"}
    assert set(tree["layer_1"]["attn"]) == {"q_a", "q_a_norm", "q_b", "kv_a",
                                            "kv_a_norm", "kv_b", "out"}
    assert set(tree["mtp"]) == {"enorm", "hnorm", "proj", "block",
                                "final_norm"}
    assert set(tree["mtp"]["block"]["ff"]) == {"router", "router_bias",
                                               "experts", "shared"}
    d = cfg.hidden_size
    assert tree["mtp"]["proj"]["kernel"].shape == (2 * d, d)
    assert tree["layer_0"]["attn"]["kv_a"]["kernel"].shape == (
        d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)    # ONE rotary key
    # counters of the three expert layers, the module's block among them
    assert float(aux["moe_dropped"]) == 0.0
    assert float(aux["moe_dense_calls"]) == (0.0 if with_kernels else 3.0)
    # which lowering the four latent layers took, asked of the record
    shut = None if with_kernels else "no Mosaic backend"
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    latent = "latent attention", (40, 4, nope, rope, cfg.v_head_dim)
    assert lowering_record.why_not(*latent) == shut
    assert lowering_record.first_refusal(
        ("rotary", sparse_lm._pair_key(40, lanes, rope))
        for lanes in (4 * rope, rope)) == shut
    if with_kernels:
        assert lowering_record.recorded(*latent) == {
            "why_not": None, "sliced": None}
    # the sentences, whole, as the operator reads them
    said = sparse_lm.engagement_records(cfg)
    widths = "128 + 64 | 128" if with_kernels else "16 + 8 | 16"
    assert said["attn_layout"] == (
        f"latent 48 / 32 + one rotary key of {rope}, heads 4 x ({widths}), "
        + ("blockwise 512: 4 of 4 layers, 2 heads a step, backward: one "
           "kernel a tile, rotary (one pass on the lanes: 4 of 4 layers)"
           if with_kernels else
           "dense XLA lowering (no Mosaic backend), rotary (XLA: no Mosaic "
           "backend)"))
    assert said["mtp_layout"] == (
        "one prediction module after the final norm: [norm(next token's "
        "embedding) ; norm(last state)] . W_eh, one expert layer, a final "
        "norm of its own; shares the embedding and the head; loss_mtp over "
        "T - 2 positions, weight 0.3")
    assert said["attn_operands"] == (
        "latent: q_nope, k_nope, v read where q_b and kv_b wrote them, "
        "delta in the backward kernel: 4 of 4 layers" if with_kernels else
        "sliced: no Mosaic backend")
    # both uses of the head, the main loss's and the prediction module's,
    # traced the rule that makes the gradients in the loss's pass
    assert said["head_layout"] == (
        "gradients made with the loss: 2 of 2 calls (main, mtp), 5 chunks "
        "of 16 rows, dW added in float32 and carried in float32")


@pytest.mark.parametrize("state, text_seq_len, rotary", [
    ("taken", 24, "one pass on the lanes: 4 of 4 layers"),
    ("refused", 20, "XLA: 36 rows are not whole sublane tiles of 8"),
    ("no_backend", 24, "XLA: no Mosaic backend"),
])
def test_attn_layout_says_which_lowering_the_rotary_took(
        state, text_seq_len, rotary, monkeypatch, lowering_record):
    """The record's last words, from what the traced calls did: the pass
    on every latent layer (the prediction module's among them), the rule's
    refusal of the local shapes, or a backend with no Mosaic kernels."""
    cfg = JoyAILMConfig(**dict(TINY, **KERNEL_WIDTHS,
                               text_seq_len=text_seq_len))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET",
                        state != "no_backend")
    model = sparse_lm.build(cfg)
    assert sparse_lm.engagement_records(cfg)["attn_layout"].endswith(
        ", rotary (XLA: no Mosaic backend)" if state == "no_backend"
        else ", rotary (XLA: none traced)")
    loss, _ = jax.jit(model.apply)(_params(cfg), *_batch(cfg))
    assert np.isfinite(float(loss))
    said = sparse_lm.engagement_records(cfg)["attn_layout"]
    assert said.endswith(f", rotary ({rotary})"), said
    assert "interleaved pairs" not in said
    # the queries' 4 x 64 rotary lanes and the one key: each was traced
    # (with no backend the gate answers for both)
    for lanes in (256, 64):
        assert lowering_record.why_not("rotary", sparse_lm._pair_key(
            cfg.total_seq_len, lanes, 64)) == {
                "taken": None, "no_backend": "no Mosaic backend"}.get(
                    state, rotary[len("XLA: "):])


@pytest.mark.parametrize("axes", [dict(dp=2, fsdp=2, tp=2),
                                  dict(dp=4, fsdp=2, tp=1)],
                         ids=["tp_splits_the_heads", "samples_only"])
def test_latent_attention_on_a_mesh_is_the_one_device_layer(
        axes, monkeypatch, lowering_record):
    """One layer, kernels interpreted, value and every gradient: where
    ``tp`` splits the heads the rotary pass gets the queries' rotary part
    as an array of its own (a shard's whole pairs of heads), else it reads
    ``q_b``'s output where it lies; the key always ``kv_a``'s."""
    from dalle_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = JoyAILMConfig(**dict(TINY, **KERNEL_WIDTHS))
    a = jax.random.normal(jax.random.PRNGKey(0),
                          (8, cfg.total_seq_len, cfg.hidden_size))

    def value_and_grads(mesh):
        mod = sparse_lm.LatentAttention(cfg, mesh=mesh, name="attn")
        params = mod.init(jax.random.PRNGKey(1), a)
        return jax.jit(jax.value_and_grad(
            lambda p, a: jnp.sum(mod.apply(p, a) ** 2), (0, 1)))(params, a)

    mesh = make_mesh(**axes)
    value, grads = value_and_grads(mesh)
    # a shard's rotary lanes of the queries, and the one key, took the pass
    tokens, tp = cfg.total_seq_len, axes["tp"]
    assert lowering_record.first_refusal(
        ("rotary", key) for key in (
            sparse_lm._pair_key(tokens, 4 * 64, 64, tp),
            sparse_lm._pair_key(tokens, 64, 64))) is None
    assert lowering_record.recorded("latent attention", sparse_lm._latent_key(
        tokens, 4, 128, 64, 128, tp))["sliced"] == (
            "a mesh axis splits the heads of each part, not the lanes of "
            "q_b's and kv_b's outputs" if tp > 1 else None)
    # the kernels too: a shard's heads of each part as arrays of their own
    # where tp splits them, else q_b's and kv_b's outputs where they lie
    said = sparse_lm.engagement_records(cfg, mesh)["attn_operands"]
    assert said == (
        "sliced: a mesh axis splits the heads of each part, not the lanes "
        "of q_b's and kv_b's outputs" if axes["tp"] > 1 else
        "latent: q_nope, k_nope, v read where q_b and kv_b wrote them, "
        "delta in the backward kernel: 4 of 4 layers"), said
    want, want_grads = value_and_grads(None)
    assert float(value) == pytest.approx(float(want), rel=5e-6)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert rel_l2(g, r) < 5e-6


def test_the_pair_pass_in_the_model_is_its_xla_lowering(monkeypatch,
                                                        lowering_record):
    """Loss and every gradient leaf of the whole tiny model with the rotary
    as the one pass on the lanes (interpreted) against the same model with
    ``rotary_interleaved_lanes``, every other kernel running on both
    sides: within the limits the yardstick's comparison has."""
    cfg = JoyAILMConfig(**dict(TINY, **KERNEL_WIDTHS))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    params = _params(cfg)
    text, image = _batch(cfg)
    model = sparse_lm.build(cfg)

    def loss_and_grads():
        # a new function a lowering: nothing traced before is reused
        return jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, text, image)[0]))(params)

    loss, grads = loss_and_grads()
    rotaries = [("rotary", sparse_lm._pair_key(cfg.total_seq_len, lanes, 64))
                for lanes in (4 * 64, 64)]
    assert lowering_record.first_refusal(rotaries) is None
    monkeypatch.setattr(sparse_lm.head_norm, "pairs_fit",
                        lambda *shape: "refused here")
    ref_loss, ref_grads = loss_and_grads()
    assert lowering_record.first_refusal(rotaries) == "refused here"
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5, jax.tree_util.keystr(path)


# bfloat16 activations, enough tokens (768 a sequence) and experts (64, 8
# held) that a few dozen of a layer's top-8 sets are near-ties
NEAR_TIES = dict(TINY, hidden_size=128, num_hidden_layers=4, expert_width=64,
                 num_experts=64, experts_per_token=8, experts_held=8,
                 expert_offset=0, vocab_size=512, text_seq_len=512,
                 image_grid=16, vocab_text=256, vocab_image=256,
                 dtype="bfloat16", head_chunk=256, dense_width=256)


@pytest.mark.parametrize("kept", [True, False])
def test_the_backward_pass_differentiates_the_experts_the_forward_ran(
        kept, monkeypatch):
    """A rematerialised layer keeps the sets its tokens chose
    (``KEPT_OF_A_LAYER``): against the reference **at the program's sets**
    the router's and the routed experts' gradients are then as close as
    the shared expert's, which reads the same rows and chooses nothing.
    ``kept`` False is the program before PR 44: XLA's CPU backend rounds
    the replay's scores otherwise, the top-k taken again flips near-ties,
    and the routed leaves read several times the shared expert's distance
    (0.05 to 0.065 against 0.013 here; the v5e's replay took the same
    sets at the cell's size, PERF.md section 6)."""
    if not kept:
        monkeypatch.setattr(sparse_lm, "KEPT_OF_A_LAYER",
                            ("attn_out", "attn_stats"))
    cfg = JoyAILMConfig(**NEAR_TIES)
    cfg.validate()
    params = sparse_lm.init_params(sparse_lm.build(cfg),
                                   jax.random.PRNGKey(1))
    text, image = _batch(cfg, n=1)
    model = sparse_lm.build(cfg)

    def loss_and_sets(p):
        (loss, _), sown = model.apply(p, text, image,
                                      mutable=["intermediates"])
        return loss, sown["intermediates"]
    (_, sown), grads = jax.jit(jax.value_and_grad(
        loss_and_sets, has_aux=True))(params)
    layers = [sown[f"layer_{i}"] for i in range(1, cfg.num_hidden_layers)]
    chosen = np.stack([np.asarray(layer["chosen"][0])
                       for layer in layers + [sown["mtp"]["block"]]])
    _, at_sets = Y.loss_and_grads_at(chosen, params, text, image,
                                     as_file(cfg))
    routed, shared = [], []
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(at_sets)):
        name = jax.tree_util.keystr(path)
        if "['router']" in name or "['experts']" in name:
            routed.append(rel_l2(g, r))
        elif "['shared']" in name:
            shared.append(rel_l2(g, r))
    assert len(routed) == 16 and len(shared) == 12
    assert 0.005 < max(shared) < 0.025      # bfloat16's own distance
    if kept:
        assert max(routed) < 1.5 * max(shared)
    else:
        assert max(routed) > 3 * max(shared)


@pytest.mark.parametrize("tokens, heads, block", [
    (256, 4, 128),      # whole blocks
    (200, 2, 128),      # an odd length that pads
    (3 * 128 + 7, 2, 128),
])
def test_the_latent_kernels_are_the_dense_lowering(tokens, heads, block):
    """Forward values and every cotangent, the shared key's (summed over
    the heads inside the kernel) among them, interpreted; nothing of the
    band is left out (no window: every tile at or under the diagonal)."""
    keys = jax.random.split(jax.random.PRNGKey(tokens), 6)
    shape = lambda lanes: (2, tokens, lanes)
    q_nope, k_nope, v, w = (jax.random.normal(k, shape(heads * 128))
                            for k in keys[:4])
    q_rope = jax.random.normal(keys[4], shape(heads * 64))
    k_rope = jax.random.normal(keys[5], shape(64))
    operands = (q_nope, q_rope, k_nope, k_rope, v)
    assert kernels.latent_fits(tokens, heads, 128, 64, 128, 4) is None

    def latent(q_nope, q_rope, k_nope, k_rope, v):
        # the parts as arrays of their own, each read at offset 0
        return kernels.latent_attention(q_nope, q_rope, (k_nope, v), k_rope,
                                        block, True)

    with jax.default_matmul_precision("highest"):
        out, grads = jax.value_and_grad(lambda *a: jnp.sum(latent(*a) * w),
                                        argnums=range(5))(*operands)
        want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(
            sparse_lm.dense_latent_attention(*a) * w),
            argnums=range(5))(*operands)
        values = latent(*operands)
    np.testing.assert_allclose(
        values, sparse_lm.dense_latent_attention(*operands), atol=2e-5)
    assert float(out) == pytest.approx(float(want), rel=1e-5)
    for name, g, r in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"),
                          grads, want_grads):
        assert g.shape == r.shape and rel_l2(g, r) < 2e-6, name
    assert grads[3].shape == (2, tokens, 64)


def _whole_operands(tokens, heads, dtype, batch=2):
    """``q_b``'s and ``kv_b``'s outputs as the layer lays them out (every
    head's nope lanes, then the unrotated rope lanes; every head's k_nope,
    then every head's v), the rotated parts, and a cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(tokens + heads), 5)
    widths = (heads * 192, heads * 64, heads * 256, 64, heads * 128)
    return [jax.random.normal(k, (batch, tokens, w)).astype(dtype)
            for k, w in zip(keys, widths)]


@pytest.mark.parametrize("tokens, heads, block, dtype", [
    (256, 4, 128, jnp.float32),             # two pairs of heads
    (200, 6, 128, jnp.float32),             # three, a length that pads
    (3 * 128 + 7, 4, 128, jnp.bfloat16),    # the cell's dtype
])
def test_the_latent_kernels_read_the_projections_outputs_where_they_lie(
        tokens, heads, block, dtype):
    """``q`` and ``kv`` whole (``q_nope`` column block ``j``, ``k_nope``
    column block ``j`` and ``v`` column block ``H/2 + j`` of 256 lanes)
    against the same entry handed the three slices at offset 0: the same
    tiles reach the same kernel bodies, so the values and every cotangent
    agree bit for bit; the cotangents come back in the operands' form, and
    ``q``'s unrotated rope lanes, which this call does not read, get
    exactly nought."""
    q, q_rope, kv, k_rope, w = _whole_operands(tokens, heads, dtype)
    lanes = heads * 128

    def whole(q, q_rope, kv, k_rope):
        return kernels.latent_attention(q, q_rope, kv, k_rope, block, True)

    def sliced(q, q_rope, kv, k_rope):
        return kernels.latent_attention(
            q[..., :lanes], q_rope, (kv[..., :lanes], kv[..., lanes:]),
            k_rope, block, True)

    both = lambda f: jax.value_and_grad(lambda *a: jnp.sum(
        (f(*a) * w).astype(jnp.float32)), argnums=range(4))(
            q, q_rope, kv, k_rope)
    np.testing.assert_array_equal(whole(q, q_rope, kv, k_rope),
                                  sliced(q, q_rope, kv, k_rope))
    (value, grads), (want, want_grads) = both(whole), both(sliced)
    assert float(value) == float(want)
    for name, g, r in zip(("q", "q_rope", "kv", "k_rope"), grads,
                          want_grads):
        assert g.shape == r.shape and g.dtype == dtype, name
        np.testing.assert_array_equal(g, r, err_msg=name)
    assert grads[0].shape == q.shape and grads[2].shape == kv.shape
    assert not np.any(np.asarray(grads[0][..., lanes:], np.float32))
    assert np.any(np.asarray(grads[2][..., lanes:], np.float32))


@pytest.mark.parametrize("tokens, heads, block", [(256, 4, 128),
                                                  (200, 6, 128)])
def test_the_backward_kernels_own_delta_gives_the_dense_gradient(
        tokens, heads, block):
    """The whole operands' cotangents against the plain gradient of the
    dense lowering in f32: ``delta`` = rowsum(do * o) is summed inside the
    backward kernel, a head and query block, and differs from any other
    order of the 128 products by f32 rounding (the same 2e-6 the kernels
    with ``delta`` as XLA code were held to)."""
    q, q_rope, kv, k_rope, w = _whole_operands(tokens, heads, jnp.float32)
    lanes = heads * 128

    def dense(q, q_rope, kv, k_rope):
        return sparse_lm.dense_latent_attention(
            q[..., :lanes], q_rope, kv[..., :lanes], k_rope, kv[..., lanes:])

    with jax.default_matmul_precision("highest"):
        grads, want_grads = (jax.grad(lambda *a: jnp.sum(f(*a) * w),
                                      argnums=range(4))(q, q_rope, kv, k_rope)
                             for f in (lambda *a: kernels.latent_attention(
                                 *a, block, True), dense))
    for name, g, r in zip(("q", "q_rope", "kv", "k_rope"), grads,
                          want_grads):
        assert g.shape == r.shape and rel_l2(g, r) < 2e-6, name


def test_the_grad_step_slices_neither_q_b_nor_kv_b(monkeypatch,
                                                   lowering_record):
    """The tiny model's gradient lowered for a TPU from here (the Mosaic
    dispatch forced; nothing runs): no ``stablehlo.slice`` of an array as
    wide as ``q_b``'s or ``kv_b``'s output is traced, where the program
    before PR 46 cut ``q_nope`` and ``k_nope`` / ``v`` out of them once a
    layer and direction, and the record says every layer's kernels read
    them where they lie."""
    import re
    cfg = JoyAILMConfig(**dict(TINY, **KERNEL_WIDTHS))
    cfg.validate()
    monkeypatch.setattr(lowering_record, "mosaic", lambda: True)
    model = sparse_lm.build(cfg)
    params = jax.eval_shape(
        lambda: sparse_lm.init_params(model, jax.random.PRNGKey(0)))
    text, image = _batch(cfg)
    lowered = jax.jit(jax.grad(
        lambda p: model.apply(p, text, image)[0])).trace(params).lower(
            lowering_platforms=("tpu",)).as_text()
    kernels_in = re.findall(r'kernel_name = "(_latent_\w+)"', lowered)
    assert kernels_in.count("_latent_fwd_kernel") == 4 \
        and kernels_in.count("_latent_bwd_kernel") == 4, kernels_in
    for width in (cfg.num_heads * 192, cfg.num_heads * 256):
        assert f"x{width}xf32>" in lowered
        cut = re.findall(r"stablehlo\.slice[^\n]*: \(tensor<(?:\d+x)*%dx\w+>\)"
                         % width, lowered)
        assert not cut, cut
    assert sparse_lm.engagement_records(cfg)["attn_operands"] == (
        "latent: q_nope, k_nope, v read where q_b and kv_b wrote them, "
        "delta in the backward kernel: 4 of 4 layers")
    # and with the parts sliced first, as the dense lowering has them (and
    # a tp axis), it shows
    monkeypatch.setattr(sparse_lm.kernels, "latent_fits",
                        lambda *shape: "the test says so")
    sliced = jax.jit(jax.grad(
        lambda p: model.apply(p, text, image)[0])).trace(params).lower(
            lowering_platforms=("tpu",)).as_text()
    assert re.findall(r"stablehlo\.slice[^\n]*: \(tensor<(?:\d+x)*1024x\w+>\)",
                      sliced)


def test_latent_fits_says_why_not():
    fits = kernels.latent_fits
    assert fits(8192, 32, 128, 64, 128, 2) is None          # the cell's
    assert "not 128 + 64 | 128" in fits(8192, 32, 64, 64, 128, 2)
    assert "not 128 + 64 | 128" in fits(40, 4, 16, 8, 16, 4)
    assert "are not pairs" in fits(8192, 3, 128, 64, 128, 2)
    # the one-kernel backward's accumulators grow with the sequence
    assert "MiB of VMEM, over 64" in fits(16384, 32, 128, 64, 128, 2)


def test_the_rotary_turns_interleaved_pairs_as_complex_numbers():
    """Lanes (2i, 2i + 1) of every head are one complex number, turned by
    ``pos * theta^(-2i / d)``: written out with numpy's complex multiply;
    the program's shift-by-a-lane form and the yardstick's agree with it."""
    theta, d, heads, t = 3.2e7, 8, 3, 11
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                     (2, t, heads * d)), np.float64)
    z = x.reshape(2, t, heads, d // 2, 2)
    z = z[..., 0] + 1j * z[..., 1]
    turn = np.exp(1j * np.arange(t)[:, None]
                  * theta ** (-2.0 * np.arange(d // 2) / d))
    z = z * turn[None, :, None, :]
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    got = sparse_lm.rotary_interleaved_lanes(jnp.asarray(x, jnp.float32), d,
                                             theta)
    np.testing.assert_allclose(got, want, atol=2e-5)
    ref = Y.rotary_pairs(jnp.asarray(x, jnp.float32).reshape(2, t, heads, d),
                         theta)
    np.testing.assert_allclose(ref.reshape(x.shape), want, atol=2e-5)
    # position 0 is left as it is, and a pair keeps its length
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
    pairs = lambda a: np.asarray(a).reshape(2, t, -1, 2)
    np.testing.assert_allclose(np.linalg.norm(pairs(got), axis=-1),
                               np.linalg.norm(pairs(x), axis=-1), rtol=1e-4)


TINY_FLAGS = [
    "--hidden-size", "64", "--num-hidden-layers", "3", "--num-heads", "4",
    "--num-kv-heads", "4", "--expert-width", "32", "--num-experts", "8",
    "--experts-per-token", "2", "--experts-held", "4", "--expert-offset",
    "2", "--vocab-size", "96", "--text-seq-len", "24", "--image-grid", "4",
    "--vocab-text", "48", "--vocab-image", "48", "--dtype", "float32",
    "--head-chunk", "16", "--dense-width", "96", "--q-lora-rank", "48",
    "--kv-lora-rank", "32"]
# no flag sets a head's three widths (``no_flag``): the preset's own
AS_FLAGGED = {**TINY, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128}


def test_the_preset_trains_through_the_peers_normal_path(lowering_record):
    """``run_trainer --preset joyaiflash`` (+ tiny field flags): the parser
    builds the preset's own class, TrainingTask the model its configuration
    names, and train_loop runs it with the swarm optimizer; the rows of the
    trainer's ring carry the model's records and the two losses."""
    from dalle_tpu.obs.trace import default_tracer
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    args = run_trainer.build_parser().parse_args(
        ["--preset", "joyaiflash", *TINY_FLAGS,
         "--per-device-batch", "1", "--grad-accum-steps", "2",
         "--target-batch-size", str(1 << 30), "--seed", "7"])
    configs = run_trainer.configs_from_args(args)
    assert configs[0] == JoyAILMConfig(**AS_FLAGGED)
    task = TrainingTask(*configs)
    assert family(task.model_cfg) is sparse_lm
    assert isinstance(task.model, sparse_lm.SparseLM)
    losses = []
    with task:
        train_loop(task, max_steps=3, warmup_steps=1,
                   on_step=lambda n, loss: losses.append(loss))
    assert len(losses) == 3 and all(np.isfinite(losses))
    rows = [r for r in default_tracer().dump() if r.get("plane") == "train"]
    warm = [r for r in rows if r["phase"] == "setup/warmup"][-1]["a"]
    # the sentences, whole, as the operator reads them (from an empty
    # record: the token-major sum has no gate, and another test's sum of
    # these shapes in this process would be this model's too)
    assert warm["moe_layout"] == (
        "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, x2.5, "
        "a shared expert of 32, layers 0-0 dense 96, no exchange: 8 devices, "
        "data parallel; token-major sums: none traced (the dense lowering)")
    assert warm["attn_operands"] == "sliced: no Mosaic backend"
    assert warm["attn_layout"] == (
        "latent 48 / 32 + one rotary key of 64, heads 4 x (128 + 64 | 128), "
        "dense XLA lowering (no Mosaic backend), rotary (XLA: no Mosaic "
        "backend)")
    assert warm["mtp_layout"].startswith("one prediction module after the "
                                         "final norm")
    steps = [r for r in rows if r["phase"] == "loop/step"][-3:]
    for row, loss in zip((r["a"] for r in steps), losses):
        assert row["moe_dropped"] == 0.0
        # the dense lowering in each of the three expert layers (the
        # module's among them) of every shard
        assert row["moe_dense_calls"] == 3.0 * task.mesh.size
        assert row["loss_main"] + 0.3 * row["loss_mtp"] == pytest.approx(
            loss, rel=1e-5)
    assert task.model_cfg.optimizer_stacking()["stacked_experts"] == 4


def test_the_preset_is_a_class_of_its_own_and_the_parents_keep_theirs():
    """``benchmark/configs/{smallthinker21b,trinitymini}.json`` hold
    ``asdict`` of the two parent classes: what the new class states as
    fields are class attributes there, and no key of theirs is new."""
    sparse = {f.name for f in dataclasses.fields(SparseLMConfig)}
    afmoe = {f.name for f in dataclasses.fields(AfmoeLMConfig)}
    joyai = {f.name for f in dataclasses.fields(JoyAILMConfig)}
    assert len(sparse) == 27 and len(afmoe) == 39
    added = joyai - afmoe
    assert added == {"q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                     "qk_rope_head_dim", "v_head_dim", "rope_interleave",
                     "num_nextn_predict_layers", "mtp_loss_weight"}
    for parent in (SparseLMConfig(), AfmoeLMConfig()):
        assert not set(dataclasses.asdict(parent)) & added
        assert not any(getattr(parent, name) for name in added)   # off
    cfg = joyaiflash_model_config()
    assert type(cfg) is JoyAILMConfig and isinstance(cfg, AfmoeLMConfig)
    cfg.validate()
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        2048, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.num_heads) == (128, 64, 128, 32)
    assert (cfg.dense_width, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, cfg.route_scale) == (7168, 768, 256, 8,
                                                        2.5)
    assert (cfg.rope_theta, cfg.rms_eps) == (3.2e7, 1e-6)
    assert {cfg.kind_of_layer(i) for i in range(5)} == {"full_rope"}
    flags = {a.dest for a in run_trainer.build_parser()._actions}
    assert {"q_lora_rank", "kv_lora_rank", "num_nextn_predict_layers"} \
        <= flags
    stated = set(JoyAILMConfig.no_flag) - set(AfmoeLMConfig.no_flag)
    assert stated == {"qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                      "rope_interleave", "mtp_loss_weight"}
    assert not flags & stated
    # the kind and the class's widths decide: ``full_rope`` without
    # ``kv_lora_rank`` is grouped key-value heads with rotary over the whole
    # sequence (tests/test_lfm2_model.py), any class's
    SparseLMConfig(layer_kinds=("full_rope",)).validate()
    with pytest.raises(ValueError, match="unknown layer kind"):
        SparseLMConfig(layer_kinds=("latent",)).validate()
    with pytest.raises(ValueError, match="interleaved pairs"):
        dataclasses.replace(cfg, rope_interleave=False).validate()
    with pytest.raises(ValueError, match="one prediction module"):
        dataclasses.replace(cfg, num_nextn_predict_layers=2).validate()


@pytest.mark.parametrize("cli, argv", [
    (run_inference, ["--checkpoint-dir", "x", "--tokenizer-path", "y",
                     "--query", "a cat"]),
    (run_server, ["--random-init"]),
    (run_aux_peer, []),
])
def test_entry_points_that_decode_refuse_the_preset_at_start(cli, argv):
    with pytest.raises(SystemExit) as refused:
        cli.main(["--preset", "joyaiflash", *argv])
    message = str(refused.value)
    assert "joyaiflash" in message and "models/decode.py" in message
    assert "latent attention" in message and "prediction module" in message
    assert message.count(".") <= 3 and "\n" not in message   # one sentence
