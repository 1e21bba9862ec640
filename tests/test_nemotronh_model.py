"""``NemotronHLMConfig`` (preset ``twotower30b``) through models/sparse_lm.py
at a tiny size, seeded random weights, f32: the family's cases over its row
(tests/sparse_family.py), and what only it has: the chunked scan is the
token-by-token recurrence, with its replay, whatever the length; the taps
are a plain causal depthwise convolution; a one-part layer has exactly its
part's leaves; experts of two products whose width ends in half a lane tile
go through the grouped kernels as they are. (Group 16 are cases of
``test_smallthinker_attention.py``'s.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, NemotronHLMConfig,
                              SparseLMConfig, twotower30b_model_config)
from dalle_tpu.models import attention, decode, sparse_lm
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped
from sparse_family import as_file, batch, rel_l2

Y = Manifest().yardstick("nemotronh")

# a mixer, an expert layer, an attention layer and a second expert layer,
# one part each; a sequence (43: no other test file's) of two fields that
# is no whole number of chunks; half of the router's experts held
TINY = dict(hidden_size=64, num_hidden_layers=4,
            layer_kinds=("mamba2", "experts", "full_nope", "experts"),
            num_heads=4, num_kv_heads=2, head_dim=16, expert_width=32,
            shared_expert_width=48, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, text_seq_len=27,
            image_grid=4, vocab_text=48, vocab_image=48, dtype="float32",
            head_chunk=16, mamba_num_heads=4, mamba_head_dim=8, ssm_groups=2,
            ssm_state_size=16, ssm_chunk=8)
# the widths the kernels take (interpreted): 128-wide heads, a hidden size
# of one lane tile, experts whose width ends in half a lane tile (as 1 856)
KERNEL_WIDTHS = dict(head_dim=128, num_heads=2, num_kv_heads=1,
                     hidden_size=128, expert_width=192,
                     shared_expert_width=128)


def _patched(name, make):
    """The yardstick's function ``name`` replaced by ``make(plain)``."""
    def patch(monkeypatch, model):
        monkeypatch.setattr(Y, name, make(getattr(Y, name)))
        return model
    return patch


def _norm_then_gate(plain):
    def gated_norm(y, z, scale, groups, eps):
        return plain(y, jnp.full_like(z, 1.2784645), scale, groups, eps) \
            * jax.nn.silu(z)      # silu(1.2784645) = 1: the norm alone
    return gated_norm


# what each mechanism is when it is left out of the REFERENCE (a key of
# ``model`` where it has one, else a patch of the yardstick's module)
LEFT_OUT = {
    "the convolution's bias": _patched(
        "causal_taps", lambda plain: lambda xbc, taps, bias: plain(
            xbc, taps, 0.0 * bias)),
    "the skip D": _patched(
        "recurrence", lambda plain: lambda x, bm, cm, dt, a, d: plain(
            x, bm, cm, dt, a, 0.0 * d)),
    "the gate before the norm (norm first, gate after)": _patched(
        "gated_norm", _norm_then_gate),
    "the gated norm's groups (one norm over all lanes)": _patched(
        "gated_norm", lambda plain: lambda y, z, scale, groups, eps: plain(
            y, z, scale, 1, eps)),
    "dt_bias": _patched(
        "mamba2", lambda plain: lambda a, ssm, model: plain(
            a, dict(ssm, dt_bias=0.0 * ssm["dt_bias"]), model)),
    "the square of the ReLU": _patched(
        "relu2", lambda plain: jax.nn.relu),
    "the shared expert": _patched(
        "ungated_block", lambda plain: lambda m, w: 0.0 * m),
    "the scale 2.5": dict(route_scale=1.0),
    "the decay (a state that never forgets)": _patched(
        "mamba2", lambda plain: lambda a, ssm, model: plain(
            a, dict(ssm, A_log=ssm["A_log"] - 30.0), model)),
}


# the preset's deployment at a small width: 16 shares of 8 consecutive
# experts, 128 in all, top 6, two-product experts (no gate) beside a shared
# expert of a width of its own; 2 shares of 16, of a width that ends in half
# a lane tile, where the kernels run interpreted
SHARES = {kernels: (2 if kernels else 16, dict(
    {k: TINY[k] for k in ("vocab_size", "text_seq_len", "image_grid",
                          "vocab_text", "vocab_image", "dtype", "head_chunk")},
    hidden_size=128 if kernels else 64, expert_width=192 if kernels else 32,
    shared_expert_width=96, num_experts=16 if kernels else 128,
    experts_held=8, expert_offset=0, experts_per_token=6))
    for kernels in (False, True)}


class TestTwotower30b(fam.Family, fam.MechanismsLeftOut, fam.SharesAddUp,
                      fam.BlockOnTheTile):
    config, preset = NemotronHLMConfig, "twotower30b"
    preset_config, Y = staticmethod(twotower30b_model_config), Y
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 2
    BLOCKWISE = {"full_nope": (None, 256)}
    LEFT_OUT, SHARES = LEFT_OUT, SHARES
    # the mixer's out-projection at the other projections' scale (not the
    # source's 1 / sqrt(52) of it), so that what the mixer computes counts
    # in the loss
    EVERYTHING = dict(TINY, residual_rescale_layers=0)
    # ``up`` and the activation in one kernel and the cotangent on the tile
    # against the two products a direction, which a block that does not fit
    # VMEM runs
    BLOCK = dict(fields=dict(TINY, **KERNEL_WIDTHS), vmem=64 * 1024,
                 refusal="a block of 128 x 192 and the tiles need ")
    ADDED = {"conv_kernel", "conv_bias", "one_part_layers", "expert_gated",
             "shared_expert_width", "mamba_num_heads", "mamba_head_dim",
             "ssm_groups", "ssm_state_size", "ssm_chunk",
             "residual_rescale_layers"}
    NOT_NOUGHT_ELSEWHERE = ("expert_gated",)
    # every width is the source's
    PUBLISHED = dict(
        hidden_size=2688, num_heads=32, num_kv_heads=2, head_dim=128,
        mamba_num_heads=64, mamba_head_dim=64, mamba_inner=4096,
        ssm_groups=8, ssm_state_size=128, conv_kernel=4, ssm_chunk=128,
        mamba_conv_lanes=6144, expert_width=1856, shared_width=3712,
        num_experts=128, experts_per_token=6, experts_held=8,
        route_scale=2.5, rms_eps=1e-5, vocab_size=16384, hidden_act="relu2")
    REFUSAL, REFUSAL_STOPS = ("state-space mixer", "'mamba2'"), None

    def the_yardstick_also(self, *, cfg, tree, shut, said, grads,
                           with_kernels, lowering_record, **_):
        """The grouped products of the two-product experts (width 192 = 1.5
        lane tiles); the two sides order their sums differently also in the
        chunked scan against the token recurrence."""
        # a one-part layer has its one norm and its part's leaves, no others
        assert set(tree) == {"token_emb", "lm_head", "final_norm", "layer_0",
                             "layer_1", "layer_2", "layer_3"}
        assert set(tree["layer_0"]) == {"norm", "ssm"}
        assert set(tree["layer_0"]["ssm"]) == {
            "in_proj", "taps", "conv_bias", "dt_bias", "A_log", "D", "norm",
            "out_proj"}
        assert set(tree["layer_2"]) == {"norm", "attn"}
        assert set(tree["layer_2"]["attn"]) == {"q", "k", "v", "out"}
        for layer in ("layer_1", "layer_3"):
            assert set(tree[layer]) == {"norm", "ff"}
            assert set(tree[layer]["ff"]) == {"router", "router_bias",
                                              "experts", "shared"}
            assert set(tree[layer]["ff"]["experts"]) == {"up", "down"}
            assert set(tree[layer]["ff"]["shared"]) == {"up", "down"}
        d, f = cfg.hidden_size, cfg.expert_width
        ssm = tree["layer_0"]["ssm"]
        assert ssm["in_proj"]["kernel"].shape == (d, 32 + (32 + 2 * 32) + 4)
        assert ssm["taps"].shape == (4, 96) and ssm["conv_bias"].shape == (96,)
        assert ssm["norm"].shape == (32,) and ssm["D"].shape == (4,)
        assert tree["layer_1"]["ff"]["experts"]["up"].shape == (4, d, f)
        assert tree["layer_1"]["ff"]["experts"]["down"].shape == (4, f, d)
        assert tree["layer_1"]["ff"]["shared"]["up"]["kernel"].shape == (
            d, cfg.shared_expert_width)
        # every leaf of the mixer has a gradient
        for name, leaf in grads["params"]["layer_0"]["ssm"].items():
            assert float(jnp.abs(jax.tree.leaves(leaf)[0]).max()) > 0, name
        # which lowering the scan took, asked of the record
        scan = sparse_lm.SCAN_SITE, sparse_lm._scan_key(43, cfg)
        assert scan[1] == (43, 4, 8, 2, 16, 8)
        # the tiny mixer's shapes are none the scan's kernels take
        refusal = "43 tokens are not whole chunks of 8"
        assert lowering_record.why_not(*scan) == (
            refusal if with_kernels else shut)
        assert said["ssm_layout"].startswith(
            "Mamba-2 mixer: 1 of 4 layers, 4 heads x 8, 2 groups of B and C, "
            "state 16, 4 taps with a bias over 96 lanes; chunked scan: chunks "
            "of 8, 6 a sequence of 43")
        assert "no (T, T) array and no state a token" in said["ssm_layout"]
        assert f"ssm/scan is XLA code ({refusal if with_kernels else shut})" \
            in said["ssm_layout"]
        # nor any the two passes take: parts and groups of no whole lane tile
        taps_refusal = "a part of 32 lanes is not whole 128-lane tiles"
        norm_refusal = "a group of 16 lanes is not whole 128-lane tiles"
        assert (f"taps, bias and SiLU: XLA code "
                f"({taps_refusal if with_kernels else shut}); gate and group "
                f"norm: XLA code ({norm_refusal if with_kernels else shut}); "
                ) in said["ssm_layout"]
        assert "conv_layout" not in said
        assert said["layer_loop"].endswith(
            "one part a layer behind one norm: mamba2 experts full_nope "
            "experts")
        assert said["attn_layout"].startswith(
            f"blockwise 512: {int(with_kernels)} of 1 attention layers, 1 "
            "full no-rope + 0 window 0 rope, 2 query heads a key-value head")
        assert "a shared expert of " + str(cfg.shared_expert_width) \
            in said["moe_layout"]
        if with_kernels:
            assert said["moe_layout"].endswith(
                "expert block: " + sparse_lm.UNGATED_ON_THE_TILE)
            assert sparse_lm.UNGATED_ON_THE_TILE.startswith(
                "two products an expert, not gated")

    def the_block_also(self, cfg, aux, refusal):
        assert refusal.startswith(self.BLOCK["refusal"])
        assert sparse_lm._block_key(cfg.hidden_size, cfg.expert_width,
                                    "float32", gated=False)[-1] == "ungated"
        assert sparse_lm._block_words(refusal, gated=False).startswith(
            "two products a direction, not gated (a block of 128 x 192")

    def the_normal_path_also(self, *, names, warm, **_):
        """``ssm_layout`` among the records; LAMB has one trust ratio an
        expert on the two-leaf experts."""
        assert sum("['ssm']['A_log']" in name for name in names) == 1
        assert sum("['experts']['up']" in name for name in names) == 2
        assert not any("['gate']" in name for name in names)
        assert warm["ssm_layout"].startswith(
            "Mamba-2 mixer: 1 of 4 layers, 4 heads x 8")
        assert ("ssm/scan is XLA code (no Mosaic backend), its backward plain "
                "differentiation of the chunked form; taps, bias and SiLU: "
                "XLA code (no Mosaic backend); gate and group norm: XLA code "
                "(no Mosaic backend); the replay keeps") in warm["ssm_layout"]
        assert warm["layer_loop"] == (
            "unrolled: 4 layers, each rematerialised but its attention, one "
            "part a layer behind one norm: mamba2 experts full_nope experts")
        assert warm["attn_layout"].startswith(
            "blockwise 512: 0 of 1 attention layers, 1 full no-rope")
        assert warm["moe_layout"].startswith(
            "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, "
            "x2.5, a shared expert of 48, no exchange: 8 devices, data "
            "parallel")
        assert "conv_layout" not in warm and "mtp_layout" not in warm

    def the_class_also(self, cfg, flags):
        for parent in fam.CHAIN:
            if parent is not NemotronHLMConfig:
                assert parent().expert_gated and not parent().one_part_layers
                assert not (parent().mamba_num_heads
                            or parent().shared_expert_width)
        assert AfmoeLMConfig().shared_width == 1024        # 1 x expert_width
        assert cfg.conv_bias and cfg.selection_bias and cfg.route_norm
        assert not (cfg.attention_gate or cfg.qk_norm or cfg.sandwich_norms
                    or cfg.mup_enabled or cfg.num_dense_layers
                    or cfg.tied_embeddings or cfg.kv_lora_rank)
        assert [cfg.kind_of_layer(i) for i in range(7)] == [
            "mamba2", "experts", "mamba2", "experts", "mamba2", "full_nope",
            "experts"]
        assert {"mamba_num_heads", "ssm_chunk", "shared_expert_width"} <= flags
        assert not {"one_part_layers", "expert_gated",
                    "residual_rescale_layers"} & flags
        # the source's rescale_prenorm_residual: a mixer's out-projection is
        # U(+-1 / sqrt(fan_in)) over the root of the published depth
        assert cfg.residual_rescale_layers == 52
        drawn = sparse_lm.init_params(sparse_lm.build(self.tiny()),
                                      jax.random.PRNGKey(0))["params"]
        for leaf, fan_in in (
                (drawn["layer_0"]["ssm"]["out_proj"]["kernel"], 32),
                (drawn["layer_2"]["attn"]["out"]["kernel"], 64),
                (drawn["layer_1"]["ff"]["experts"]["down"], 32),
                (drawn["layer_1"]["ff"]["shared"]["down"]["kernel"], 48)):
            bound = (fan_in * 52) ** -0.5
            assert 0.9 * bound < float(jnp.abs(leaf).max()) <= bound
            assert float(leaf.std()) == pytest.approx(bound / 3 ** 0.5,
                                                      rel=0.08)
        # ... and no other leaf: the up-projection keeps its unit fan-in scale
        up = drawn["layer_1"]["ff"]["experts"]["up"]
        assert float(up.std()) == pytest.approx(64 ** -0.5, rel=0.1)
        # the kinds: each needs a class that states it
        with pytest.raises(ValueError, match="state-space mixer"):
            SparseLMConfig(layer_kinds=("mamba2",)).validate()
        with pytest.raises(ValueError, match="no operator"):
            AfmoeLMConfig(layer_kinds=("experts",)).validate()
        with pytest.raises(ValueError, match="leave an expert layer"):
            dataclasses.replace(cfg, layer_kinds=("mamba2",)).validate()
        with pytest.raises(ValueError, match="'mamba2', 'full_nope' or"):
            dataclasses.replace(
                cfg, layer_kinds=("window_rope", "experts")).validate()
        with pytest.raises(ValueError, match="unknown hidden_act 'silu'"):
            dataclasses.replace(cfg, hidden_act="silu").validate()
        with pytest.raises(ValueError, match="multiple of"):
            dataclasses.replace(cfg, ssm_groups=7).validate()
        # a gated class does not take the square
        with pytest.raises(ValueError, match="unknown hidden_act"):
            AfmoeLMConfig(hidden_act="relu2").validate()


# a mixer the scan's kernels take: heads of half a lane tile, a state and a
# chunk of one, a sequence of two chunks
SCAN_WIDTHS = dict(mamba_num_heads=4, mamba_head_dim=64, ssm_groups=2,
                   ssm_state_size=128, ssm_chunk=128, text_seq_len=240,
                   num_hidden_layers=3,
                   layer_kinds=("mamba2", "experts", "mamba2"))


def test_a_mixer_of_lane_tiles_takes_the_scans_kernels(monkeypatch,
                                                       lowering_record):
    """Two mixer layers (an expert layer between) whose scan runs the
    kernel pair, interpreted, under the layers' rematerialisation: loss and
    every gradient leaf against the yardstick at the limits of the XLA
    lowering, and ``ssm_layout`` says which lowering ran."""
    cfg = NemotronHLMConfig(**dict(TINY, **SCAN_WIDTHS))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    params, (text, image) = fam.params(cfg), batch(cfg)
    # (a program of its own: the record is asked what this trace did)
    (loss, _), grads = fam.system(cfg, params, text, image, anew=True)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image, as_file(cfg))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    fam.leaves_within(grads, ref_grads, 2e-5)
    key = sparse_lm._scan_key(256, cfg)
    assert lowering_record.recorded(sparse_lm.SCAN_SITE, key) == {
        "why_not": None, "chunks_a_step": 2,
        "backward": sparse_lm.SCAN_BACKWARD}
    layout = sparse_lm.engagement_records(cfg)["ssm_layout"]
    assert layout.startswith(
        "Mamba-2 mixer: 2 of 3 layers, 4 heads x 64, 2 groups of B and C, "
        "state 128, 4 taps with a bias over 768 lanes; chunked scan: chunks "
        "of 128, 2 a sequence of 256")
    assert "ssm/scan is a pair of Pallas kernels (2 chunks a grid step" \
        in layout
    assert "backward: one kernel, the chunks in reverse" in layout
    assert ("taps, bias and SiLU: one pass a direction, x, B and C written "
            "apart; gate and group norm: one pass a direction; the replay "
            "keeps nothing of the mixer but the layer's input") in layout
    for site, key in ((sparse_lm.TAPS_SITE, sparse_lm._taps_key(256, cfg)),
                      (sparse_lm.GATE_NORM_SITE,
                       sparse_lm._gate_norm_key(256, cfg))):
        assert lowering_record.recorded(site, key) == {"why_not": None}


@pytest.mark.parametrize("tokens, chunk", [
    (43, 8),        # no whole number of chunks: padded behind
    (5, 8),         # shorter than a chunk
    (8, 8),         # one chunk
    (64, 8),        # many
    (40, 128),      # the preset's chunk, a sequence inside it
])
def test_the_chunked_scan_is_the_token_recurrence(tokens, chunk):
    """:func:`chunked_scan` against the yardstick's ``lax.scan`` over the
    tokens: the result, and every operand's gradient through a
    rematerialised call (the replay), f32."""
    h, p, g, n = 4, 8, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(tokens), 6)
    x = jax.random.normal(keys[0], (2, tokens, h * p))
    bm = jax.random.normal(keys[1], (2, tokens, g * n))
    cm = jax.random.normal(keys[2], (2, tokens, g * n))
    dt = jax.nn.softplus(jax.random.normal(keys[3], (2, tokens, h)))
    a = -jnp.exp(jax.random.normal(keys[4], (h,)))
    d = jax.random.normal(keys[5], (h,))
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def chunked(x, bm, cm, dt, a, d):
        y = jax.checkpoint(lambda *o: sparse_lm.chunked_scan(
            *o, heads=h, groups=g, chunk=chunk))(x, bm, cm, dt, a, d)
        return jnp.sum(y * w), y

    def by_token(x, bm, cm, dt, a, d):
        y = Y.recurrence(
            x.reshape(2, tokens, g, h // g, p), bm.reshape(2, tokens, g, n),
            cm.reshape(2, tokens, g, n), dt.reshape(2, tokens, g, h // g),
            a.reshape(g, h // g), d.reshape(g, h // g)).reshape(x.shape)
        return jnp.sum(y * w), y

    operands = (x, bm, cm, dt, a, d)
    with jax.default_matmul_precision("highest"):
        (_, got), grads = jax.jit(jax.value_and_grad(
            chunked, range(6), has_aux=True))(*operands)
        (_, want), ref = jax.jit(jax.value_and_grad(
            by_token, range(6), has_aux=True))(*operands)
    assert got.shape == x.shape
    assert rel_l2(got, want) < 2e-6
    for name, g_, r in zip("x B C dt A D".split(), grads, ref):
        assert rel_l2(g_, r) < 1e-5, name


def test_the_taps_are_causal_and_a_plain_depthwise_convolution():
    """``causal_taps_silu`` against ``lax.conv_general_dilated`` with one
    group a lane on a sequence padded in front, bias and SiLU after; a
    later token moves no earlier output; the yardstick's is the same."""
    k, lanes, t = 4, 24, 11
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (2, t, lanes))
    taps = jax.random.normal(keys[1], (k, lanes))
    bias = jax.random.normal(keys[2], (lanes,))
    got = sparse_lm.causal_taps_silu(x, taps, bias)
    conv = jax.lax.conv_general_dilated(
        jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))), taps[:, None, :], (1,),
        "VALID", dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=lanes, precision="highest")
    np.testing.assert_allclose(got, jax.nn.silu(conv + bias), atol=2e-6)
    np.testing.assert_allclose(got, Y.causal_taps(x, taps, bias), atol=2e-6)
    moved = sparse_lm.causal_taps_silu(x.at[:, 6].add(1.0), taps, bias)
    np.testing.assert_array_equal(moved[:, :6], got[:, :6])
    assert float(jnp.abs(moved[:, 6:6 + k] - got[:, 6:6 + k]).min()) > 0
    np.testing.assert_array_equal(moved[:, 6 + k:], got[:, 6 + k:])
    # a sequence shorter than the taps
    short = sparse_lm.causal_taps_silu(x[:, :2], taps, bias)
    np.testing.assert_allclose(short, got[:, :2], atol=2e-6)



@pytest.mark.parametrize("width", [192, 320])
def test_a_width_that_ends_in_half_a_lane_tile_goes_through_the_kernels(
        width, monkeypatch, lowering_record):
    """Two-product experts of a width = 64 mod 128 (as the preset's 1 856)
    through the sorted lowering's grouped kernels, interpreted, with the
    leaves as they are: the result, the count and every cotangent equal
    the dense lowering's (every held expert on every token)."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    n, d, held, k = 96, 128, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(width), 5)
    m = jax.random.normal(keys[0], (n, d))
    # two experts of the router's 8 a token, never the same one twice
    idx = jax.random.randint(keys[1], (n, 1), 0, 7) + jnp.array([[0, 1]])
    p = jax.nn.softmax(jax.random.normal(keys[2], (n, k)))
    up = jax.random.normal(keys[3], (held, d, width)) * 0.1
    down = jax.random.normal(keys[4], (held, width, d)) * 0.1
    w = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    rows = 256

    def out(lowering):
        def f(m, p, up, down):
            y, computed = lowering(m, idx, p, up, down)
            return jnp.sum(y * w), (y, computed)
        return jax.jit(jax.value_and_grad(f, range(4), has_aux=True))(
            m, p, up, down)

    with jax.default_matmul_precision("highest"):
        (_, (y, computed)), grads = out(lambda *o: sparse_lm.held_experts(
            *o, offset=2, rows=rows, act="relu2"))
        (_, (want, here)), ref = out(lambda *o: sparse_lm._every_expert(
            *o, offset=2, act="relu2"))
    assert lowering_record.why_not(
        sparse_lm.PRODUCTS_SITE, (rows, held, d, width)) is None
    assert float(computed) == float(here) > 0
    np.testing.assert_allclose(y, want, atol=2e-5)
    for name, g, r in zip(("m", "p", "up", "down"), grads, ref):
        assert g.shape == r.shape and rel_l2(g, r) < 1e-5, name
    assert grads[2].shape == (held, d, width)        # unpadded


def test_the_grouped_kernels_rule_takes_half_a_lane_tile_and_no_less():
    why_not = sparse_lm.grouped_kernels_why_not
    assert why_not(2688, 1856) is None and why_not(2688, 3712) is None
    assert why_not(2048, 768) is None           # the accepted widths stay
    assert why_not(2688, 1800) == "2688 x 1800 are not lane tiles"
    assert why_not(2700, 1856) == "2700 x 1856 are not lane tiles"
    # one weight block a kernel: the preset's experts fit VMEM
    assert grouped.block_why_not(2688, 1856, "bfloat16", gated=False) is None
    assert grouped.block_why_not(2688, 1856, "bfloat16") is None
    assert "a block of 8192 x 8192" in grouped.block_why_not(
        8192, 8192, "bfloat16", gated=False)
    with pytest.raises(ValueError, match="no ungated expert"):
        grouped.act_cotangent("silu", jnp.ones(2), jnp.ones(2))


def test_decode_refuses_the_kind_by_name():
    decode.refuse_recurrent_layers(SparseLMConfig())        # no such layer
    with pytest.raises(NotImplementedError, match="'mamba2'"):
        decode.refuse_recurrent_layers(NemotronHLMConfig())
    decode.refuse_selected_layers(NemotronHLMConfig())      # not its kind
