"""``AfmoeLMConfig`` (preset ``trinitymini``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: the family's cases over its row
(tests/sparse_family.py), and what only it has: one test a mechanism with a
switch on both sides, which fails if the mechanism is left out; the head
norms and the rotary in one pass; the leading dense layer; the router's
bias."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, SparseLMConfig,
                              trinitymini_model_config)
from dalle_tpu.models import attention, sparse_lm
from sparse_family import as_file, batch

Y = Manifest().yardstick("trinity")

# a dense layer and one period: four window layers and a full one, a
# sequence (32: no other test file's, the dispatchers' records are a
# process's) longer than the window, half of the router's experts held
TINY = dict(hidden_size=64, num_hidden_layers=5, num_heads=4, num_kv_heads=2,
            head_dim=16, expert_width=32, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, window=8,
            text_seq_len=16, image_grid=4, vocab_text=48, vocab_image=48,
            dtype="float32", head_chunk=16, dense_width=96)
KERNEL_WIDTHS = dict(head_dim=128)


class TestTrinitymini(fam.Family, fam.SharesAddUp, fam.BlockOnTheTile):
    config, preset = AfmoeLMConfig, "trinitymini"
    preset_config, Y = staticmethod(trinitymini_model_config), Y
    # the four expert layers only
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 4
    BLOCKWISE = {"window_rope": (8, 256), "full_nope": (None, 256)}
    # 8 experts over 4 shares of 2 (``expert_offset`` 0, 2, 4, 6)
    SHARES = dict.fromkeys((False, True), (4, dict(TINY, experts_held=2)))
    # gated-SiLU experts beside a shared expert under a sigmoid router
    BLOCK = dict(fields=TINY, vmem=64 * 1024,
                 refusal="need 0.6 MiB of VMEM, over 0.0625")
    ADDED = {"num_dense_layers", "dense_width", "num_shared_experts",
             "hidden_act", "score_func", "selection_bias", "route_norm",
             "route_scale", "attention_gate", "qk_norm", "sandwich_norms",
             "mup_enabled"}
    # fixed for the parent class, off but for these three
    NOT_NOUGHT_ELSEWHERE = ("hidden_act", "score_func", "route_scale")
    PUBLISHED = {}
    REFUSAL = ("gated attention", "shared expert", "dense gated block")

    def the_yardstick_also(self, *, cfg, tree, shut, said, with_kernels,
                           lowering_record, **_):
        """With the kernels the attention, the grouped products and the
        token-major sums run their Pallas kernels, interpreted."""
        kinds = [cfg.kind_of_layer(i) for i in range(5)]
        assert kinds == ["window_rope"] * 4 + ["full_nope"]
        assert [cfg.layer_is_dense(i) for i in range(5)] == (
            [True] + [False] * 4)
        # ... through the one-pass head norm, where the kernels run
        for rotary in (True, False):
            assert lowering_record.first_refusal(
                ("head norm" + " + rotary" * rotary,
                 (32, heads * cfg.head_dim, cfg.head_dim))
                for heads in (4, 2)) == shut
        # the whole sentence, as the operator reads it
        assert said["attn_layout"] == (
            "blockwise 512: 5 of 5 layers, 1 full no-rope + 4 window 8 rope, "
            "2 query heads a key-value head, backward: one kernel a tile (5 "
            "of 5 layers), normed queries and keys (one pass on the lanes: 5 "
            "of 5 layers), rotary (in the head pass: 4 of 4 rope layers), "
            "gated output" if with_kernels else
            "blockwise 512: 0 of 5 layers, 1 full no-rope + 4 window 8 rope, "
            "2 query heads a key-value head, normed queries and keys (XLA: no "
            "Mosaic backend), rotary (XLA: no Mosaic backend), gated output")
        layer = tree["layer_1"]
        assert set(layer) == {"attn", "attn_norm", "post_attn_norm", "ff",
                              "ff_norm", "post_ff_norm"}          # four norms
        assert set(layer["attn"]) == {"q", "k", "v", "gate", "out", "q_norm",
                                      "k_norm"}
        assert layer["attn"]["q_norm"].shape == (cfg.head_dim,)
        assert set(layer["ff"]) == {"router", "router_bias", "experts",
                                    "shared"}
        assert set(tree["layer_0"]["ff"]) == {"dense"}

    def the_normal_path_also(self, *, warm, steps, **_):
        assert warm["moe_layout"] == (
            "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, "
            "x2.826, a shared expert of 32, layers 0-0 dense 96, no exchange: "
            "8 devices, data parallel; token-major sums: none traced (the "
            "dense lowering)")
        assert warm["attn_layout"] == (
            "blockwise 512: 0 of 5 layers, 1 full no-rope + 4 window 8 rope, "
            "2 query heads a key-value head, normed queries and keys (XLA: no "
            "Mosaic backend), rotary (XLA: no Mosaic backend), gated output")
        for row in steps:
            assert 0 < row["moe_assignments_here_pct"] < 100

    def the_class_also(self, cfg, flags):
        """The mechanism switches are fields a configuration's file states
        and no entry point's flags."""
        assert SparseLMConfig().hidden_act == "relu"
        assert SparseLMConfig().score_func == "softmax"
        assert SparseLMConfig().route_scale == 1.0
        assert {"num_dense_layers", "dense_width"} <= flags
        assert not flags & (set(AfmoeLMConfig.no_flag) - {"tied_embeddings"})
        with pytest.raises(ValueError, match="sigmoid"):
            dataclasses.replace(cfg, score_func="softmax").validate()
        with pytest.raises(ValueError, match="softmax over the chosen"):
            SparseLMConfig(router_softmax_over_chosen=False).validate()


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


@pytest.fixture(scope="module")
def with_everything():
    cfg = AfmoeLMConfig(**SMALL)
    params, (text, image) = fam.params(cfg), batch(cfg)
    (loss, _), _ = fam.system(cfg, params, text, image)
    return cfg, params, text, image, float(loss)


@pytest.mark.parametrize("mechanism", list(LEFT_OUT))
def test_a_mechanism_left_out_is_told(mechanism, with_everything,
                                      monkeypatch):
    """The system with every mechanism against the reference without this
    one: they disagree. The system without it against the reference
    without it: they agree, so both read the same key."""
    cfg, params, text, image, loss = with_everything
    without = dataclasses.replace(cfg, **LEFT_OUT[mechanism])
    without.validate()
    if mechanism == "the gated SiLU":
        # the reference is written for SiLU only: the system's ReLU differs
        (other, _), _ = fam.system(without, params, text, image)
        assert abs(float(other) - loss) > 1e-5 * loss
        return
    lacking, _ = jax.jit(lambda p: Y.loss_fn(p, text, image,
                                             as_file(without)))(params)
    if mechanism != "the weights' normalisation":
        assert abs(float(lacking) - loss) > 4e-6 * loss
    params = fam.params(without)
    (loss, _), grads = fam.system(without, params, text, image)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image,
                                           as_file(without))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    fam.leaves_within(grads, ref_grads, 2e-5)


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
        fam.trace(cfg)
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
    """Loss and every gradient leaf of a tiny model of one layer of each
    kind (the pass's two shapes of call: it is held to a layer's gradients,
    not to a stack of the same layer) whose rope layer norms and rotates
    queries and keys in one pass and whose full layer norms them in it
    (interpreted), against the same model with the reshaped ``rms_norm``
    and ``apply_rotary_lanes``: the same f32 model to its rounding."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = AfmoeLMConfig(**dict(TINY, head_dim=128, num_hidden_layers=2,
                               num_dense_layers=0,
                               layer_kinds=("window_rope", "full_nope")))
    # the queries' 4 heads and the keys' 2, with the rotary and without
    took = lambda: {(rotary, lowering_record.why_not(
        "head norm" + " + rotary" * rotary,
        (cfg.total_seq_len, heads * 128, 128)))
        for rotary in (True, False) for heads in (4, 2)}
    taken, refused = fam.a_pass_is_its_xla_lowering(
        cfg, took, lambda: monkeypatch.setattr(
            sparse_lm.head_norm, "fits", lambda *a: "the test says so"))
    assert taken == {(True, None), (False, None)}
    assert {why for _, why in refused} == {"the test says so"}


def test_attn_layout_names_the_split_backward_between_the_other_words(
        monkeypatch, lowering_record):
    """Where ``dk`` and ``dv`` do not fit VMEM (the budget shrunk, as no
    preset's length reaches) the word says so and why, after the heads and
    before the head norms' words."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    monkeypatch.setattr(sparse_lm.kernels, "VMEM_LIMIT_BYTES", 2 ** 20)
    cfg = AfmoeLMConfig(**dict(SMALL, head_dim=128))
    fam.trace(cfg)
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
    params, (text, image) = fam.params(without), batch(cfg)
    assert "router" in params["params"]["layer_0"]["ff"]
    (loss, aux), _ = fam.system(without, params, text, image)
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

