"""``AfmoeLMConfig`` (preset ``trinitymini``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: against the plain reference of its
yardstick; one test a mechanism, which fails if the mechanism is left out;
the shares of the expert layer with the shared expert counted once add up
to the uncut layer; and the preset trains through the peer's normal path
(run_trainer's parser, TrainingTask, train_loop)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.cli import run_aux_peer, run_inference, run_server, run_trainer
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig,
                              NemotronHLMConfig, SparseLMConfig,
                              trinitymini_model_config)
from dalle_tpu.models import attention, family, sparse_lm
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped

Y = Manifest().yardstick("trinity")

# a dense layer and one period: four window layers and a full one, a
# sequence (32: no other test file's, the dispatchers' records are a
# process's) longer than the window, half of the router's experts held
TINY = dict(hidden_size=64, num_hidden_layers=5, num_heads=4, num_kv_heads=2,
            head_dim=16, expert_width=32, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, window=8,
            text_seq_len=16, image_grid=4, vocab_text=48, vocab_image=48,
            dtype="float32", head_chunk=16, dense_width=96)


# ``JoyAILMConfig`` (preset ``joyaiflash``) at the same tiny size: its
# yardstick, and the widths of its latent attention; its own tests are
# tests/test_joyai_model.py, the cases below the ones both share
YJ = Manifest().yardstick("joyai")
YN = Manifest().yardstick("nemotronh")
JOYAI_TINY = dict(
    {k: v for k, v in TINY.items() if k not in ("head_dim", "window")},
    num_hidden_layers=2, num_kv_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


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


def _system(cfg, params, text, image):
    model = sparse_lm.build(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, image), has_aux=True))(params)


@pytest.mark.parametrize("kernels", [False, True])
def test_loss_and_every_gradient_leaf_against_the_yardstick(
        kernels, monkeypatch, lowering_record):
    """The whole tiny model with every mechanism on; with ``kernels`` the
    attention, the grouped products and the token-major sums run their
    Pallas kernels, interpreted."""
    cfg = AfmoeLMConfig(**dict(TINY, head_dim=128 if kernels else 16))
    cfg.validate()
    kinds = [cfg.kind_of_layer(i) for i in range(5)]
    assert kinds == ["window_rope"] * 4 + ["full_nope"]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", kernels)
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, aux), grads = _system(cfg, params, text, image)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image, as_file(cfg))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5, jax.tree_util.keystr(path)
    # ... through the one-pass head norm, where the kernels run, and the
    # blockwise attention's one-kernel backward
    shut = None if kernels else "no Mosaic backend"
    for kind, rotary in (("window_rope", True), ("full_nope", False)):
        call = f"{kind} attention", (32, 4 * cfg.head_dim, 2 * cfg.head_dim)
        assert lowering_record.why_not(*call) == shut
        if kernels:
            assert lowering_record.recorded(*call) == {
                "why_not": None, "split_backward": None,
                "band": sparse_lm.kernels.band_account(
                    1, 512, 8 if rotary else None, 256)}
        assert lowering_record.first_refusal(
            ("head norm" + " + rotary" * rotary,
             (32, heads * cfg.head_dim, cfg.head_dim))
            for heads in (4, 2)) == shut
    # the whole sentence, as the operator reads it
    assert sparse_lm.engagement_records(cfg)["attn_layout"] == (
        "blockwise 512: 5 of 5 layers, 1 full no-rope + 4 window 8 rope, 2 "
        "query heads a key-value head, backward: one kernel a tile (5 of 5 "
        "layers), normed queries and keys (one pass on the lanes: 5 of 5 "
        "layers), rotary (in the head pass: 4 of 4 rope layers), gated "
        "output" if kernels else
        "blockwise 512: 0 of 5 layers, 1 full no-rope + 4 window 8 rope, 2 "
        "query heads a key-value head, normed queries and keys (XLA: no "
        "Mosaic backend), rotary (XLA: no Mosaic backend), gated output")
    layer = params["params"]["layer_1"]
    assert set(layer) == {"attn", "attn_norm", "post_attn_norm", "ff",
                          "ff_norm", "post_ff_norm"}          # four norms
    assert set(layer["attn"]) == {"q", "k", "v", "gate", "out", "q_norm",
                                  "k_norm"}
    assert layer["attn"]["q_norm"].shape == (cfg.head_dim,)
    assert set(layer["ff"]) == {"router", "router_bias", "experts",
                                "shared"}
    assert set(params["params"]["layer_0"]["ff"]) == {"dense"}
    # no gradient reaches the router's bias, on either side: exact zeros
    for i in range(1, 5):
        for tree in (grads, ref_grads):
            bias = tree["params"][f"layer_{i}"]["ff"]["router_bias"]
            assert bias.shape == (8,) and not np.asarray(bias).any()
    # counters of the four expert layers only
    assert 0 < float(aux["moe_assignments_here_pct"]) < 100
    assert float(aux["moe_dropped"]) == 0.0
    assert float(aux["moe_dense_calls"]) == (0.0 if kernels else 4.0)


# what each mechanism is when it is left out; for every one the reference
# reads the same key of ``model``. (The results' norms take a token's scale
# out again, so leaving ``route_norm`` out moves the loss by 2e-6 of it:
# the router's own test below tells it, by the weights' sum.)
LEFT_OUT = {
    "the shared expert": dict(num_shared_experts=0),
    "the attention's output gate": dict(attention_gate=False),
    "the head norms of queries and keys": dict(qk_norm=False),
    "the two norms of the results (four a layer)":
        dict(sandwich_norms=False),
    "the embedding's scale": dict(mup_enabled=False),
    "the weights' normalisation": dict(route_norm=False),
    "the weights' scale": dict(route_scale=1.0),
    "rotary on sliding layers only":
        dict(layer_kinds=("window_rope",) * 2),
    "the gated SiLU": dict(hidden_act="relu"),
}


# a dense layer, then a full expert layer: every mechanism in two layers
SMALL = dict(TINY, num_hidden_layers=2,
             layer_kinds=("window_rope", "full_nope"))


# ``joyaiflash``'s mechanisms have no switch on either side (the program
# and the reference are written for them): each is left out of the
# REFERENCE by a patch of the yardstick's module, or of what it reads
def _no_latent_norms(monkeypatch, model):
    plain = YJ._rms_norm
    latents = (model["q_lora_rank"], model["kv_lora_rank"])
    monkeypatch.setattr(YJ, "_rms_norm", lambda x, g, eps: (
        x if g.shape[0] in latents else plain(x, g, eps)))
    return model


def _no_shared_rotary_key(monkeypatch, model):
    # the one key every head reads (B, T, rope) adds nothing to a score
    plain = YJ.rotary_pairs
    monkeypatch.setattr(YJ, "rotary_pairs", lambda x, theta: (
        jnp.zeros_like(x) if x.ndim == 3 else plain(x, theta)))
    return model


def _scale_of_the_unrotated_part_alone(monkeypatch, model):
    plain = YJ._attention
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    up = ((nope + rope) / nope) ** 0.5        # 1 / sqrt(nope) in all
    monkeypatch.setattr(YJ, "_attention", lambda qn, qr, *rest: plain(
        qn * up, qr * up, *rest))
    return model


JOYAI_LEFT_OUT = {
    "the norms of the two latents": _no_latent_norms,
    "the shared rotary key": _no_shared_rotary_key,
    "the scale 1 / sqrt(nope + rope)": _scale_of_the_unrotated_part_alone,
    "the prediction module's loss":
        lambda monkeypatch, model: dict(model, mtp_loss_weight=0.0),
    "the prediction module":
        lambda monkeypatch, model: dict(model, num_nextn_predict_layers=0),
}


@pytest.fixture(scope="module")
def with_everything():
    cfg = AfmoeLMConfig(**SMALL)
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, _), _ = _system(cfg, params, text, image)
    return cfg, params, text, image, float(loss)


@pytest.mark.parametrize("mechanism", [*LEFT_OUT, *JOYAI_LEFT_OUT])
def test_a_mechanism_left_out_is_told(mechanism, with_everything,
                                      monkeypatch):
    """The system with every mechanism against the reference without this
    one: they disagree. The system without it against the reference
    without it: they agree, so both read the same key."""
    if mechanism in JOYAI_LEFT_OUT:
        return _a_joyaiflash_mechanism_left_out_is_told(mechanism,
                                                        monkeypatch)
    cfg, params, text, image, loss = with_everything
    without = dataclasses.replace(cfg, **LEFT_OUT[mechanism])
    without.validate()
    if mechanism == "the gated SiLU":
        # the reference is written for SiLU only: the system's ReLU differs
        (other, _), _ = _system(without, params, text, image)
        assert abs(float(other) - loss) > 1e-5 * loss
        return
    lacking, _ = jax.jit(lambda p: Y.loss_fn(p, text, image,
                                             as_file(without)))(params)
    if mechanism != "the weights' normalisation":
        assert abs(float(lacking) - loss) > 4e-6 * loss
    params = _params(without)
    (loss, _), grads = _system(without, params, text, image)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image,
                                           as_file(without))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5


def _a_joyaiflash_mechanism_left_out_is_told(mechanism, monkeypatch):
    """The system against the reference whole agrees; against the reference
    without the mechanism it does not (ten times the distance at which
    they agree). The module, which has a field, is also left out of both."""
    cfg = JoyAILMConfig(**JOYAI_TINY)
    cfg.validate()
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, aux), _ = _system(cfg, params, text, image)
    whole, _ = jax.jit(lambda p: YJ.loss_fn(p, text, image,
                                            as_file(cfg)))(params)
    assert float(loss) == pytest.approx(float(whole), rel=2e-6)
    assert float(aux["loss_main"] + cfg.mtp_loss_weight * aux["loss_mtp"]) \
        == pytest.approx(float(loss), rel=1e-6)
    without = JOYAI_LEFT_OUT[mechanism](monkeypatch, as_file(cfg))
    lacking, _ = jax.jit(lambda p: YJ.loss_fn(p, text, image,
                                              without))(params)
    assert abs(float(lacking) - float(loss)) > 2e-5 * float(loss)
    if mechanism == "the prediction module":
        cfg = dataclasses.replace(cfg, num_nextn_predict_layers=0)
        params = _params(cfg)
        assert "mtp" not in params["params"]
        (loss, aux), grads = _system(cfg, params, text, image)
        assert "loss_mtp" not in aux and "loss_main" not in aux
        ref_loss, ref_grads = YJ.loss_and_grads(params, text, image,
                                                as_file(cfg))
        assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            assert rel_l2(g, r) < 2e-5


@pytest.mark.parametrize("interpret, head_dim, words, rotary", [
    (True, 128, "(one pass on the lanes: 2 of 2 layers)",
     "(in the head pass: 1 of 1 rope layers)"),
    (True, 64, "(XLA: head_dim 64 is not whole 128-lane tiles)", None),
    (False, 128, "(XLA: no Mosaic backend)", None),
    (None, 128, "(XLA: none traced)", None),
])
def test_attn_layout_says_which_lowering_the_head_norms_took(
        interpret, head_dim, words, rotary, monkeypatch, lowering_record):
    """Read from what the traced calls did, as the blockwise count is: the
    head norms of every layer, and the rotary of the rope layers, which
    runs in the norm's pass or, for the norm's own reason, as XLA.
    (``None``: kernels there are, and nothing was traced.)"""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret is not False)
    cfg = AfmoeLMConfig(**dict(SMALL, head_dim=head_dim))
    if interpret is not None:
        text, image = _batch(cfg)
        jax.eval_shape(lambda p: sparse_lm.build(cfg).apply(p, text, image),
                       _params(cfg))
    layout = sparse_lm.engagement_records(cfg)["attn_layout"]
    assert layout.endswith(f"normed queries and keys {words}, rotary "
                           f"{rotary or words}, gated output")
    without = dataclasses.replace(cfg, qk_norm=False)
    assert "normed" not in sparse_lm.engagement_records(without)[
        "attn_layout"]
    # ... whose rotary would be a pass of its own, and none was traced
    assert (", rotary (XLA: none traced), gated" if interpret is not False
            else ", rotary (XLA: no Mosaic backend), gated") in sparse_lm.\
        engagement_records(without)["attn_layout"]


def test_norm_and_rotary_in_one_pass_are_the_xla_lowering(monkeypatch,
                                                          lowering_record):
    """Loss and every gradient leaf of the tiny model whose four rope
    layers norm and rotate queries and keys in one pass and whose full
    layer norms them in it (interpreted), against the same model with the
    reshaped ``rms_norm`` and ``apply_rotary_lanes``: the same f32 model
    to its rounding."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = AfmoeLMConfig(**dict(TINY, head_dim=128))
    params = _params(cfg)
    text, image = _batch(cfg)
    # the queries' 4 heads and the keys' 2, with the rotary and without
    took = lambda: {(rotary, lowering_record.why_not(
        "head norm" + " + rotary" * rotary,
        (cfg.total_seq_len, heads * 128, 128)))
        for rotary in (True, False) for heads in (4, 2)}
    (loss, _), grads = _system(cfg, params, text, image)
    assert took() == {(True, None), (False, None)}
    monkeypatch.setattr(sparse_lm.head_norm, "fits",
                        lambda *a: "the test says so")
    (ref_loss, _), ref_grads = _system(cfg, params, text, image)
    assert {why for _, why in took()} == {"the test says so"}
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 1e-5, jax.tree_util.keystr(path)


def test_attn_layout_names_the_split_backward_between_the_other_words(
        monkeypatch, lowering_record):
    """Where ``dk`` and ``dv`` do not fit VMEM (the budget shrunk, as no
    preset's length reaches) the word says so and why, after the heads and
    before the head norms' words."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    monkeypatch.setattr(sparse_lm.kernels, "VMEM_LIMIT_BYTES", 2 ** 20)
    cfg = AfmoeLMConfig(**dict(SMALL, head_dim=128))
    text, image = _batch(cfg)
    jax.eval_shape(lambda p: sparse_lm.build(cfg).apply(p, text, image),
                   _params(cfg))
    layout = sparse_lm.engagement_records(cfg)["attn_layout"]
    assert layout.startswith("blockwise 512: 2 of 2 layers, ")
    assert ("2 query heads a key-value head, backward: dq + dk/dv kernels "
            "(dk and dv of 512 tokens need 11.0 MiB of VMEM, over 1), normed "
            "queries and keys (") in layout
    assert layout.endswith(", gated output")


def test_the_leading_dense_layer():
    """With no dense layer, layer 0 is an expert layer with a router of
    its own; the dense block is what the reference's dense layer is."""
    cfg = AfmoeLMConfig(**TINY)
    without = dataclasses.replace(cfg, num_dense_layers=0)
    params = _params(without)
    assert "router" in params["params"]["layer_0"]["ff"]
    text, image = _batch(cfg)
    (loss, aux), _ = _system(without, params, text, image)
    ref_loss, _ = Y.loss_and_grads(params, text, image, as_file(without))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    assert float(aux["moe_dense_calls"]) == 5.0      # five expert layers
    m = jax.random.normal(jax.random.PRNGKey(0), (2, 7, cfg.hidden_size))
    block = sparse_lm.DenseFF(cfg)
    w = block.init(jax.random.PRNGKey(1), m)
    assert w["params"]["dense"]["gate"]["kernel"].shape == (64, 96)
    np.testing.assert_allclose(
        block.apply(w, m), Y.gated_block(m, w["params"]["dense"]),
        atol=1e-6)
    with pytest.raises(ValueError, match="leave an expert layer"):
        dataclasses.replace(cfg, num_dense_layers=5).validate()


def _router(cfg, m, bias=None):
    layer = sparse_lm.ExpertLayer(cfg)
    w = layer.init(jax.random.PRNGKey(7), m, method="route")
    assert set(w["params"]) == {"router", "router_bias"}
    if bias is not None:
        w = {"params": dict(w["params"], router_bias=bias)}
    idx, p = layer.apply(w, m, method="route")
    return w["params"], np.asarray(idx), np.asarray(p)


def test_a_bias_changes_the_chosen_set_and_not_the_weights():
    """Selection is on sigmoid(score) + bias; the weights are the chosen
    experts' sigmoids alone over their sum, times ``route_scale``: they
    sum to ``route_scale`` whatever the bias, and a token whose set a bias
    leaves unchanged keeps its weights."""
    cfg = AfmoeLMConfig(**TINY)
    m = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.hidden_size))
    w, idx0, p0 = _router(cfg, m)
    bias = 0.08 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    _, idx1, p1 = _router(cfg, m, bias)
    np.testing.assert_allclose(p0.sum(-1), cfg.route_scale, rtol=1e-6)
    np.testing.assert_allclose(p1.sum(-1), cfg.route_scale, rtol=1e-6)
    by_expert = lambda idx, p: np.take_along_axis(p, np.argsort(idx, -1), -1)
    same = (np.sort(idx0, -1) == np.sort(idx1, -1)).all(-1)
    assert same.any() and (~same).any()     # some sets change, some stay
    np.testing.assert_allclose(by_expert(idx0, p0)[same],
                               by_expert(idx1, p1)[same], rtol=1e-6)
    # the sets are the largest of score + bias, the weights unbiased
    s = np.asarray(jax.nn.sigmoid(m @ w["router"]))
    want = np.argsort(-(s + np.asarray(bias)), -1)[..., :2]
    np.testing.assert_array_equal(np.sort(idx1, -1), np.sort(want, -1))
    chosen = np.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(
        p1, cfg.route_scale * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    # and the reference's router is the same function
    ref_idx, ref_p = Y.route(m, dict(w, router_bias=bias), as_file(cfg))
    np.testing.assert_array_equal(np.sort(ref_idx, -1), np.sort(idx1, -1))
    np.testing.assert_allclose(by_expert(np.asarray(ref_idx),
                                         np.asarray(ref_p)),
                               by_expert(idx1, p1), rtol=1e-5)
    # a bias large enough puts its expert into every token's set
    _, idx2, _ = _router(cfg, m, jnp.zeros((8,)).at[5].set(2.0))
    assert (idx2 == 5).any(-1).all()


@pytest.mark.parametrize("config, kernels", [
    ("trinitymini", False), ("trinitymini", True),
    ("joyaiflash", False), ("joyaiflash", True),
    ("twotower30b", False), ("twotower30b", True)])
def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        config, kernels, monkeypatch):
    """``trinitymini``: 8 experts over 4 shares of 2 (``expert_offset`` 0,
    2, 4, 6); ``joyaiflash``: its own 32 shares of 8 consecutive experts
    (``8r .. 8r + 7``), 256 in all, top 8, at a small width (8 shares of
    64 where the kernels run interpreted); ``twotower30b``: its own 16
    shares of 8 consecutive experts, 128 in all, top 6, two-product experts
    (no gate) beside a shared expert of a width of its own (2 shares of 16,
    of a width that ends in half a lane tile, where the kernels run
    interpreted). Every share's
    layer returns its routed part plus the shared expert, which all compute
    alike; the routed parts summed plus the shared expert counted once
    equal the reference's uncut layer."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", kernels)
    if config == "trinitymini":
        base, y = AfmoeLMConfig(**dict(TINY, experts_held=2)), Y
    elif config == "twotower30b":
        small = {k: TINY[k] for k in (
            "vocab_size", "text_seq_len", "image_grid", "vocab_text",
            "vocab_image", "dtype", "head_chunk")}
        base, y = NemotronHLMConfig(**dict(
            small, hidden_size=128 if kernels else 64,
            expert_width=192 if kernels else 32, shared_expert_width=96,
            num_experts=16 if kernels else 128, experts_held=8,
            expert_offset=0, experts_per_token=6)), YN
    else:       # interpreted, 8 shares of 64 experts: a share costs 3 s
        base, y = JoyAILMConfig(**dict(
            JOYAI_TINY, num_experts=64 if kernels else 256, experts_held=8,
            expert_offset=0, experts_per_token=8)), YJ
    n, held = base.num_experts, base.experts_held
    shares = n // held
    rng = jax.random.split(jax.random.PRNGKey(3), 9)
    d, f, fs = base.hidden_size, base.expert_width, base.shared_width
    m = jax.random.normal(rng[0], (2, 28, d))
    kernel = lambda key, shape: {"kernel": jax.random.normal(key, shape)
                                 * 0.2}
    whole = {"router": jax.random.normal(rng[1], (d, n)),
             "router_bias": 0.05 * jax.random.normal(rng[2], (n,)),
             "experts": {"gate": jax.random.normal(rng[3], (n, d, f)) * 0.2,
                         "up": jax.random.normal(rng[4], (n, d, f)) * 0.2,
                         "down": jax.random.normal(rng[5], (n, f, d)) * 0.2},
             "shared": {"gate": kernel(rng[6], (d, fs)),
                        "up": kernel(rng[7], (d, fs)),
                        "down": kernel(rng[8], (fs, d))}}
    block = y.gated_block if base.expert_gated else y.ungated_block
    if not base.expert_gated:       # two leaves an expert, two a block
        del whole["experts"]["gate"], whole["shared"]["gate"]
    want = y.whole_layer_experts(m, whole, as_file(base))
    shared = block(m, whole["shared"])
    assert float(jnp.abs(shared).max()) > 0.01

    routed, here = jnp.zeros_like(m), 0.0
    for share in range(shares):
        cfg = dataclasses.replace(base, expert_offset=held * share)
        layer = sparse_lm.ExpertLayer(cfg)
        mine = {"params": dict(whole, experts={
            k: w[held * share: held * (share + 1)]
            for k, w in whole["experts"].items()})}
        idx, p = layer.apply(mine, m, method="route")    # alike on all
        y, counters = layer.apply(mine, m, idx, p)
        routed = routed + (y - shared)
        here += float(counters["here"])
    np.testing.assert_allclose(routed + shared, want, atol=5e-5)
    assert here == pytest.approx(1.0)     # every assignment, by one share
    # summing the shares' results as they come counts the shared expert
    # once a share: that is not the layer
    assert float(jnp.abs(routed + shares * shared - want).max()) > 0.01


@pytest.mark.parametrize("config", ["trinitymini", "joyaiflash"])
def test_the_expert_block_on_the_tile_is_the_same_model_to_the_last_bit(
        config, monkeypatch, lowering_record):
    """Gated-SiLU experts beside a shared expert under a sigmoid router,
    f32, the grouped kernels interpreted: loss, counters and every gradient
    leaf with the expert block's tile work in its kernels (gate, up and
    activation one kernel, the cotangents on the tile, one ``dxs``) equal
    the three products a direction with XLA code between them, which the
    model takes where two weight blocks do not fit VMEM, and says why."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = (AfmoeLMConfig(**TINY) if config == "trinitymini"
           else JoyAILMConfig(**JOYAI_TINY))
    params, (text, image) = _params(cfg), _batch(cfg)
    said = lambda: lowering_record.recorded(
        sparse_lm.PRODUCTS_SITE, sparse_lm._block_key(
            cfg.hidden_size, cfg.expert_width, cfg.dtype))["why_not"]
    layout = lambda: sparse_lm.engagement_records(cfg)["moe_layout"]
    on_the_tile = _system(cfg, params, text, image)
    assert said() is None
    assert layout().endswith("; expert block: " + sparse_lm.BLOCK_ON_THE_TILE)
    monkeypatch.setattr(grouped, "_VMEM", 64 * 1024)
    three = _system(cfg, params, text, image)
    assert "need 0.6 MiB of VMEM, over 0.0625" in said()
    assert layout().endswith(f"; expert block: three products a direction "
                             f"({said()})")
    (loss, aux), grads = on_the_tile
    assert float(aux["moe_dense_calls"]) == 0.0
    assert 0.0 < float(aux["moe_tiles_active_pct"]) <= 100.0
    assert np.isfinite(float(loss))
    for a, b in zip(jax.tree.leaves(on_the_tile), jax.tree.leaves(three),
                    strict=True):
        np.testing.assert_array_equal(a, b)


TINY_FLAGS = [
    "--hidden-size", "64", "--num-hidden-layers", "5", "--num-heads", "4",
    "--num-kv-heads", "2", "--head-dim", "16", "--expert-width", "32",
    "--num-experts", "8", "--experts-per-token", "2", "--experts-held", "4",
    "--expert-offset", "2", "--vocab-size", "96", "--window", "8",
    "--text-seq-len", "16", "--image-grid", "4", "--vocab-text", "48",
    "--vocab-image", "48", "--dtype", "float32", "--head-chunk", "16",
    "--dense-width", "96"]


def test_the_preset_trains_through_the_peers_normal_path(lowering_record):
    """``run_trainer --preset trinitymini`` (+ tiny field flags): the
    parser builds the preset's own class, TrainingTask the model its
    configuration names, and train_loop runs it with the swarm optimizer;
    the rows of the trainer's ring carry the model's records."""
    from dalle_tpu.obs.trace import default_tracer
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    args = run_trainer.build_parser().parse_args(
        ["--preset", "trinitymini", *TINY_FLAGS,
         "--per-device-batch", "1", "--grad-accum-steps", "2",
         "--target-batch-size", str(1 << 30), "--seed", "7"])
    configs = run_trainer.configs_from_args(args)
    assert configs[0] == AfmoeLMConfig(**TINY)
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
        "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, "
        "x2.826, a shared expert of 32, layers 0-0 dense 96, no exchange: "
        "8 devices, data parallel; token-major sums: none traced (the dense "
        "lowering)")
    assert warm["attn_layout"] == (
        "blockwise 512: 0 of 5 layers, 1 full no-rope + 4 window 8 rope, 2 "
        "query heads a key-value head, normed queries and keys (XLA: no "
        "Mosaic backend), rotary (XLA: no Mosaic backend), gated output")
    steps = [r for r in rows if r["phase"] == "loop/step"][-3:]
    for row in (r["a"] for r in steps):
        assert 0 < row["moe_assignments_here_pct"] < 100
        assert row["moe_dropped"] == 0.0
        # no Mosaic backend here: the dense lowering in each of the four
        # expert layers of every shard
        assert row["moe_dense_calls"] == 4.0 * task.mesh.size
    assert task.model_cfg.optimizer_stacking()["stacked_experts"] == 4


def test_the_preset_is_a_class_of_its_own_and_the_sparse_class_keeps_its():
    """``benchmark/configs/smallthinker21b.json`` holds ``asdict`` of
    ``SparseLMConfig``: what the new class states as fields are class
    attributes there, and no key is new. The mechanism switches are fields
    a configuration's file states and no entry point's flags."""
    sparse = {f.name for f in dataclasses.fields(SparseLMConfig)}
    afmoe = {f.name for f in dataclasses.fields(AfmoeLMConfig)}
    assert len(sparse) == 27 and set(dataclasses.asdict(SparseLMConfig())) \
        == sparse
    added = afmoe - sparse
    assert added == {"num_dense_layers", "dense_width", "num_shared_experts",
                     "hidden_act", "score_func", "selection_bias",
                     "route_norm", "route_scale", "attention_gate",
                     "qk_norm", "sandwich_norms", "mup_enabled"}
    for name in added:        # fixed for the parent class, off
        assert not getattr(SparseLMConfig(), name) or name in (
            "hidden_act", "score_func", "route_scale")
    assert SparseLMConfig().hidden_act == "relu"
    assert SparseLMConfig().score_func == "softmax"
    assert SparseLMConfig().route_scale == 1.0
    cfg = trinitymini_model_config()
    assert type(cfg) is AfmoeLMConfig and isinstance(cfg, SparseLMConfig)
    cfg.validate()
    flags = {a.dest for a in run_trainer.build_parser()._actions}
    assert {"num_dense_layers", "dense_width"} <= flags
    assert not flags & (set(AfmoeLMConfig.no_flag) - {"tied_embeddings"})
    with pytest.raises(ValueError, match="sigmoid"):
        dataclasses.replace(cfg, score_func="softmax").validate()
    with pytest.raises(ValueError, match="softmax over the chosen"):
        SparseLMConfig(router_softmax_over_chosen=False).validate()


@pytest.mark.parametrize("cli, argv", [
    (run_inference, ["--checkpoint-dir", "x", "--tokenizer-path", "y",
                     "--query", "a cat"]),
    (run_server, ["--random-init"]),
    (run_aux_peer, []),
])
def test_entry_points_that_decode_refuse_the_preset_at_start(cli, argv):
    with pytest.raises(SystemExit) as refused:
        cli.main(["--preset", "trinitymini", *argv])
    message = str(refused.value)
    assert "trinitymini" in message and "models/decode.py" in message
    assert "gated attention" in message and "shared expert" in message
    assert "dense gated block" in message
    assert message.count(".") <= 3 and "\n" not in message   # one sentence
