"""``JoyAILMConfig`` (preset ``joyaiflash``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: the family's cases over its row
(tests/sparse_family.py), and what only it has: the latent kernels against
the dense lowering of the same equations; the rotary's interleaved pairs
against a complex multiply; the backward pass differentiates the experts the
forward ran."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, SparseLMConfig,
                              joyaiflash_model_config)
from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from sparse_family import as_file, batch, rel_l2

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
# a dense layer and an expert layer with the prediction module: every
# mechanism in two layers, a sequence of 32
SMALL = dict(TINY, num_hidden_layers=2, text_seq_len=16)


# the mechanisms have no switch on either side (the program and the
# reference are written for them): each is left out of the REFERENCE by a
# patch of the yardstick's module, or of what it reads
def _no_latent_norms(monkeypatch, model):
    plain = Y._rms_norm
    latents = (model["q_lora_rank"], model["kv_lora_rank"])
    monkeypatch.setattr(Y, "_rms_norm", lambda x, g, eps: (
        x if g.shape[0] in latents else plain(x, g, eps)))
    return model


def _no_shared_rotary_key(monkeypatch, model):
    # the one key every head reads (B, T, rope) adds nothing to a score
    plain = Y.rotary_pairs
    monkeypatch.setattr(Y, "rotary_pairs", lambda x, theta: (
        jnp.zeros_like(x) if x.ndim == 3 else plain(x, theta)))
    return model


def _scale_of_the_unrotated_part_alone(monkeypatch, model):
    plain = Y._attention
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    up = ((nope + rope) / nope) ** 0.5        # 1 / sqrt(nope) in all
    monkeypatch.setattr(Y, "_attention", lambda qn, qr, *rest: plain(
        qn * up, qr * up, *rest))
    return model


LEFT_OUT = {
    "the norms of the two latents": _no_latent_norms,
    "the shared rotary key": _no_shared_rotary_key,
    "the scale 1 / sqrt(nope + rope)": _scale_of_the_unrotated_part_alone,
    "the prediction module's loss": dict(mtp_loss_weight=0.0),
    "the prediction module": dict(num_nextn_predict_layers=0),
}


class TestJoyaiflash(fam.Family, fam.MechanismsLeftOut, fam.SharesAddUp,
                     fam.BlockOnTheTile):
    config, preset = JoyAILMConfig, "joyaiflash"
    preset_config, Y = staticmethod(joyaiflash_model_config), Y
    # the three expert layers, the prediction module's block among them
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 3
    LEFT_OUT, EVERYTHING = LEFT_OUT, SMALL
    # its own 32 shares of 8 consecutive experts (``8r .. 8r + 7``), 256 in
    # all, top 8, at a small width; 8 shares of 64 where the kernels run
    # interpreted: a share costs 3 s
    SHARES = {interpreted: (8 if interpreted else 32, dict(
        SMALL, num_experts=64 if interpreted else 256, experts_held=8,
        expert_offset=0, experts_per_token=8))
        for interpreted in (False, True)}
    # gated-SiLU experts beside a shared expert under a sigmoid router
    BLOCK = dict(fields=SMALL, vmem=64 * 1024,
                 refusal="need 0.6 MiB of VMEM, over 0.0625")
    ADDED = {"q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "rope_interleave",
             "num_nextn_predict_layers", "mtp_loss_weight"}
    PUBLISHED = dict(
        hidden_size=2048, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_heads=32, dense_width=7168, expert_width=768, num_experts=256,
        experts_per_token=8, route_scale=2.5, rope_theta=3.2e7, rms_eps=1e-6)
    REFUSAL = ("latent attention", "prediction module")

    def the_yardstick_also(self, *, cfg, tree, shut, said, loss, aux,
                           with_kernels, lowering_record, weights, text,
                           image, **_):
        """The prediction module's loss in it; with the kernels the latent
        attention, the grouped products and the token-major sums run
        interpreted."""
        # the loss is the sum of its two parts, which ride beside it
        (_, (main, mtp)) = jax.jit(lambda p: Y.loss_fn(
            p, text, image, as_file(cfg)))(weights)
        assert float(aux["loss_main"]) == pytest.approx(float(main), rel=2e-6)
        assert float(aux["loss_mtp"]) == pytest.approx(float(mtp), rel=2e-6)
        assert float(loss) == pytest.approx(
            float(main) + cfg.mtp_loss_weight * float(mtp), rel=1e-6)
        assert set(tree) == {"token_emb", "lm_head", "final_norm", "mtp",
                             "layer_0", "layer_1", "layer_2"}
        assert set(tree["layer_1"]) == {"attn", "attn_norm", "ff", "ff_norm"}
        assert set(tree["layer_1"]["attn"]) == {
            "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "out"}
        assert set(tree["mtp"]) == {"enorm", "hnorm", "proj", "block",
                                    "final_norm"}
        assert set(tree["mtp"]["block"]["ff"]) == {"router", "router_bias",
                                                   "experts", "shared"}
        d = cfg.hidden_size
        assert tree["mtp"]["proj"]["kernel"].shape == (2 * d, d)
        assert tree["layer_0"]["attn"]["kv_a"]["kernel"].shape == (
            d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)    # ONE rotary key
        # which lowering the four latent layers took, asked of the record
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
        widths = "128 + 64 | 128" if with_kernels else "16 + 8 | 16"
        assert said["attn_layout"] == (
            f"latent 48 / 32 + one rotary key of {rope}, heads 4 x "
            f"({widths}), "
            + ("blockwise 512: 4 of 4 layers, 2 heads a step, backward: one "
               "kernel a tile, rotary (one pass on the lanes: 4 of 4 layers)"
               if with_kernels else
               "dense XLA lowering (no Mosaic backend), rotary (XLA: no "
               "Mosaic backend)"))
        assert said["mtp_layout"] == (
            "one prediction module after the final norm: [norm(next token's "
            "embedding) ; norm(last state)] . W_eh, one expert layer, a final "
            "norm of its own; shares the embedding and the head; loss_mtp "
            "over T - 2 positions, weight 0.3")
        assert said["attn_operands"] == (
            "latent: q_nope, k_nope, v read where q_b and kv_b wrote them, "
            "delta in the backward kernel: 4 of 4 layers" if with_kernels else
            "sliced: no Mosaic backend")
        # both uses of the head, the main loss's and the prediction module's,
        # traced the rule that makes the gradients in the loss's pass
        assert said["head_layout"] == (
            "gradients made with the loss: 2 of 2 calls (main, mtp), 5 chunks "
            "of 16 rows, dW added in float32 and carried in float32")

    def left_out_also(self, mechanism, cfg, loss, aux):
        """The module, which has a field, is also left out of both."""
        assert float(aux["loss_main"] + cfg.mtp_loss_weight
                     * aux["loss_mtp"]) == pytest.approx(loss, rel=1e-6)
        if mechanism == "the prediction module":
            cfg = dataclasses.replace(cfg, num_nextn_predict_layers=0)
            params, (text, image) = fam.params(cfg), batch(cfg)
            assert "mtp" not in params["params"]
            (loss, aux), grads = fam.system(cfg, params, text, image)
            assert "loss_mtp" not in aux and "loss_main" not in aux
            ref_loss, ref_grads = Y.loss_and_grads(params, text, image,
                                                   as_file(cfg))
            assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
            fam.leaves_within(grads, ref_grads, 2e-5)

    def the_normal_path_also(self, *, warm, steps, losses, **_):
        """The rows carry the model's records and the two losses."""
        assert warm["moe_layout"] == (
            "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, "
            "x2.5, a shared expert of 32, layers 0-0 dense 96, no exchange: 8 "
            "devices, data parallel; token-major sums: none traced (the dense "
            "lowering)")
        assert warm["attn_operands"] == "sliced: no Mosaic backend"
        assert warm["attn_layout"] == (
            "latent 48 / 32 + one rotary key of 64, heads 4 x (128 + 64 | "
            "128), dense XLA lowering (no Mosaic backend), rotary (XLA: no "
            "Mosaic backend)")
        assert warm["mtp_layout"].startswith("one prediction module after the "
                                             "final norm")
        for row, loss in zip(steps, losses):
            assert row["loss_main"] + 0.3 * row["loss_mtp"] == pytest.approx(
                loss, rel=1e-5)

    def the_class_also(self, cfg, flags):
        assert {cfg.kind_of_layer(i) for i in range(5)} == {"full_rope"}
        assert {"q_lora_rank", "kv_lora_rank", "num_nextn_predict_layers"} \
            <= flags
        stated = set(JoyAILMConfig.no_flag) - set(AfmoeLMConfig.no_flag)
        assert stated == {"qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                          "rope_interleave", "mtp_loss_weight"}
        assert not flags & stated
        # the kind and the class's widths decide: ``full_rope`` without
        # ``kv_lora_rank`` is grouped key-value heads with rotary over the
        # whole sequence (tests/test_lfm2_model.py), any class's
        SparseLMConfig(layer_kinds=("full_rope",)).validate()
        with pytest.raises(ValueError, match="unknown layer kind"):
            SparseLMConfig(layer_kinds=("latent",)).validate()
        with pytest.raises(ValueError, match="interleaved pairs"):
            dataclasses.replace(cfg, rope_interleave=False).validate()
        with pytest.raises(ValueError, match="one prediction module"):
            dataclasses.replace(cfg, num_nextn_predict_layers=2).validate()


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
    loss, _ = jax.jit(model.apply)(fam.params(cfg), *batch(cfg))
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
        params = jax.jit(mod.init)(jax.random.PRNGKey(1), a)
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
    """A tiny model of one latent-attention layer (the pass's two shapes of
    call, the queries' rotary lanes and the one key: it is held to a
    layer's gradients, not to a stack of the same layer and a prediction
    module) with the rotary as the one pass on the lanes against the same
    model with ``rotary_interleaved_lanes``: within the limits the
    yardstick's comparison has."""
    cfg = JoyAILMConfig(**dict(TINY, **KERNEL_WIDTHS, num_hidden_layers=1,
                               num_dense_layers=0,
                               num_nextn_predict_layers=0))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    rotaries = [("rotary", sparse_lm._pair_key(cfg.total_seq_len, lanes, 64))
                for lanes in (4 * 64, 64)]
    assert fam.a_pass_is_its_xla_lowering(
        cfg, lambda: lowering_record.first_refusal(rotaries),
        lambda: monkeypatch.setattr(sparse_lm.head_norm, "pairs_fit",
                                    lambda *shape: "refused here"),
        within=(2e-6, 2e-5)) == (None, "refused here")


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
    params, (text, image) = fam.params(cfg, moved=False), batch(cfg, n=1)
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
        # jitted: eagerly every operation of the four is a compile
        out, grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(latent(*a) * w), argnums=range(5)))(*operands)
        want, want_grads = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            sparse_lm.dense_latent_attention(*a) * w),
            argnums=range(5)))(*operands)
        values = jax.jit(latent)(*operands)
        dense_values = jax.jit(sparse_lm.dense_latent_attention)(*operands)
    np.testing.assert_allclose(values, dense_values, atol=2e-5)
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

    both = lambda f: jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        (f(*a) * w).astype(jnp.float32)), argnums=range(4)))(
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
        grads, want_grads = (
            jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w),
                             argnums=range(4)))(q, q_rope, kv, k_rope)
            for f in (lambda *a: kernels.latent_attention(*a, block, True),
                      dense))
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
    text, image = batch(cfg)
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

