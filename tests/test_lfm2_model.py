"""``Lfm2MoeLMConfig`` (preset ``lfm2moe``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: the family's cases over its row
(tests/sparse_family.py), and what only it has: the short convolution is
causal and is a plain depthwise convolution; the kernels for two 64-wide
heads a lane tile against the dense lowering; the tied table gets the sum of
both uses' gradients."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, Lfm2MoeLMConfig,
                              SparseLMConfig, lfm2moe_model_config)
from dalle_tpu.models import sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from sparse_family import batch, rel_l2

Y = Manifest().yardstick("lfm2")

# a dense convolution layer, an attention layer with experts and a
# convolution layer with experts; a sequence (44: no other test file's) of
# two fields, half of the router's experts held
TINY = dict(hidden_size=64, num_hidden_layers=3,
            layer_kinds=("short_conv", "full_rope", "short_conv"),
            num_heads=4, num_kv_heads=2, head_dim=16, expert_width=32,
            num_experts=8, experts_per_token=2, experts_held=4,
            expert_offset=2, vocab_size=96, text_seq_len=28, image_grid=4,
            vocab_text=48, vocab_image=48, dtype="float32", head_chunk=16,
            dense_width=96)
# the widths the kernels take (interpreted): 64-wide heads, lane tiles
KERNEL_WIDTHS = dict(head_dim=64, hidden_size=128, expert_width=128,
                     dense_width=128)


def _no_gate(which):
    """The reference's convolution with one of its gates left at 1."""
    def patch(monkeypatch, model):
        plain = jnp.split

        def split(x, n, axis=-1):
            parts = list(plain(x, n, axis=axis))
            parts[which] = jnp.ones_like(parts[which])
            return parts
        monkeypatch.setattr(Y.jnp, "split", split)
        return model
    return patch


def _a_tap_left_out(monkeypatch, model):
    plain = Y.short_conv
    monkeypatch.setattr(Y, "short_conv", lambda a, conv: plain(
        a, dict(conv, taps=conv["taps"].at[0].set(0.0))))
    return model


def _not_causal(monkeypatch, model):
    # the taps on the tokens t .. t + 2 and not t - 2 .. t
    plain = Y.jnp.pad
    monkeypatch.setattr(Y.jnp, "pad", lambda x, widths: plain(
        x, [w[::-1] for w in widths]))
    return model


# what each mechanism is when it is left out of the REFERENCE (a key of
# ``model`` where it has one, else a patch of the yardstick's module)
LEFT_OUT = {
    "the head norms of queries and keys": dict(qk_norm=False),
    "the rotary": dict(
        layer_kinds=["short_conv", "full_nope", "short_conv"]),
    "tying": dict(tied_embeddings=False),
    "the gate B": _no_gate(0),
    "the gate C": _no_gate(1),
    "a tap": _a_tap_left_out,
    "causality of the taps": _not_causal,
    "the convolution (attention in its place)":
        dict(layer_kinds=["full_rope"]),
}


class TestLfm2moe(fam.Family, fam.MechanismsLeftOut, fam.SharesAddUp,
                  fam.BlockOnTheTile):
    config, preset = Lfm2MoeLMConfig, "lfm2moe"
    preset_config, Y = staticmethod(lfm2moe_model_config), Y
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 2
    # two 64-wide heads a lane tile: whole tiles (``sub`` the tile)
    BLOCKWISE = {"full_rope": (None, 512)}
    LEFT_OUT, EVERYTHING = LEFT_OUT, TINY
    # the preset's deployment at a small width: 32 experts, top 4, over 4
    # shares of 8 consecutive experts (``expert_offset`` 0, 8, 16, 24); no
    # shared expert to count once, so the four summed as they come are the
    # layer
    SHARES = {False: (4, dict(TINY, num_experts=32, experts_per_token=4,
                              experts_held=8, expert_offset=0))}
    SHARES[True] = (4, dict(SHARES[False][1], **KERNEL_WIDTHS))
    # the preset at the widths its kernels take
    BLOCK = dict(fields=dict(TINY, **KERNEL_WIDTHS), vmem=256 * 1024,
                 refusal="two blocks of 128 x 128 and the tiles need 2.1 MiB "
                         "of VMEM, over 0.25")
    ADDED = {"conv_kernel", "conv_bias"}
    PUBLISHED = dict(
        hidden_size=2048, num_heads=32, num_kv_heads=8, head_dim=64,
        dense_width=7168, expert_width=1792, num_experts=32,
        experts_per_token=4, experts_held=8, route_scale=1.0, rope_theta=1e6,
        rms_eps=1e-5, vocab_size=16384, conv_kernel=3, conv_bias=False,
        tied_embeddings=True, qk_norm=True)
    REFUSAL = ("short convolution", "conv_kernel - 1")

    def the_yardstick_also(self, *, cfg, tree, shut, said, grads,
                           with_kernels, lowering_record, **_):
        """With the kernels the attention on two 64-wide heads a lane tile;
        the two sides order their sums differently also in the taps, which
        the program takes as shifts."""
        # one table: no head leaf beside it
        assert set(tree) == {"token_emb", "final_norm", "layer_0", "layer_1",
                             "layer_2"}
        assert set(tree["layer_0"]) == {"conv", "attn_norm", "ff", "ff_norm"}
        assert set(tree["layer_0"]["conv"]) == {"in_proj", "taps", "out_proj"}
        assert set(tree["layer_0"]["ff"]) == {"dense"}
        assert set(tree["layer_1"]) == {"attn", "attn_norm", "ff", "ff_norm"}
        assert set(tree["layer_1"]["attn"]) == {"q", "k", "v", "out", "q_norm",
                                                "k_norm"}
        assert set(tree["layer_2"]["ff"]) == {"router", "router_bias",
                                              "experts"}
        d = cfg.hidden_size
        assert tree["layer_2"]["conv"]["taps"].shape == (3, d)
        assert tree["layer_2"]["conv"]["in_proj"]["kernel"].shape == (d, 3 * d)
        assert tree["layer_1"]["attn"]["q_norm"].shape == (cfg.head_dim,)
        # the taps and the table have gradients
        for layer in ("layer_0", "layer_2"):
            taps = grads["params"][layer]["conv"]["taps"]
            assert float(jnp.abs(taps).min()) > 0
        assert float(jnp.linalg.norm(grads["params"]["token_emb"])) > 0
        # the head pass of 64-wide heads stays XLA code, for the rule's reason
        assert lowering_record.first_refusal(
            ("head norm + rotary", (44, heads * cfg.head_dim, cfg.head_dim))
            for heads in (4, 2)) == (
                "head_dim 64 is not whole 128-lane tiles" if with_kernels
                else shut)
        # the sentences, whole, as the operator reads them
        assert said["attn_layout"] == (
            "blockwise 512: 1 of 1 attention layers, 1 full rope, 2 heads of "
            "64 a lane tile, 2 query heads a key-value head, backward: one "
            "kernel a tile (1 of 1 layers), normed queries and keys (XLA: "
            "head_dim 64 is not whole 128-lane tiles), rotary (XLA: head_dim "
            "64 is not whole 128-lane tiles)" if with_kernels else
            "blockwise 512: 0 of 1 attention layers, 1 full rope, 2 query "
            "heads a key-value head, normed queries and keys (XLA: no Mosaic "
            "backend), rotary (XLA: no Mosaic backend)")
        assert said.get("attn_band") == (
            "1 full_rope: 1 tile, 1 at an edge whole, visited over allowed "
            "pairs 1.9961" if with_kernels else None)
        assert said["conv_layout"] == (
            "gated short convolution: 2 of 3 layers, 3 taps, causal, "
            f"depthwise over {d} lanes; conv/mix is XLA code: B, C and u read "
            f"as column blocks of in_proj's (B, T, {3 * d}) output in place, "
            "the taps as 2 shifts along the tokens in f32 (no Mosaic kernel)")
        assert said["head_layout"].startswith(
            f"tied: the head is the embedding's table (96 x {d})")
        assert said["head_layout"].endswith(
            "one LAMB trust ratio; gradients made with the loss: 1 of 1 calls "
            "(main), 6 chunks of 16 rows, dW added in float32 and carried in "
            "float32")
        assert said["moe_layout"].startswith(
            "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, x1, "
            "layers 0-0 dense ")
        assert "shared expert" not in said["moe_layout"]

    @staticmethod
    def for_the_reference(cfg, weights):
        """An ``lm_head`` and an ``attn`` for the references that read one."""
        d, extra = cfg.hidden_size, jax.random.split(jax.random.PRNGKey(9), 3)
        tree = dict(weights["params"],
                    lm_head=0.5 * jax.random.normal(extra[0],
                                                    (d, cfg.vocab_size)))
        for i in (0, 2):
            tree[f"layer_{i}"] = dict(
                tree[f"layer_{i}"], attn=weights["params"]["layer_1"]["attn"])
        return {"params": tree}

    def the_block_also(self, cfg, aux, refusal):
        """``moe_tiles_active_pct`` is the plan's: 88 tokens a call, top 2
        of 8 with 4 held, send each held expert under a tile of rows, so 4
        of the buffer's 1 + 4 tiles hold rows in both expert layers."""
        assert refusal == self.BLOCK["refusal"]
        assert sparse_lm.BLOCK_ON_THE_TILE == (
            "gate, up and activation one kernel; cotangents on the tile; "
            "one dxs; inactive tiles unmoved")
        assert float(aux["moe_tiles_active_pct"]) == pytest.approx(80.0)
        assert "moe_tiles_active_pct" in sparse_lm.step_attributes(cfg)

    def the_normal_path_also(self, *, names, warm, **_):
        """``conv_layout`` and ``head_layout`` among the records; the
        optimizer's state has one table."""
        assert sum("token_emb" in name for name in names) == 1
        assert not any("lm_head" in name for name in names)
        assert sum("['taps']" in name for name in names) == 2
        assert warm["conv_layout"].startswith(
            "gated short convolution: 2 of 3 layers, 3 taps, causal")
        assert "XLA code" in warm["conv_layout"]
        assert warm["head_layout"].startswith(
            "tied: the head is the embedding")
        assert warm["attn_layout"] == (
            "blockwise 512: 0 of 1 attention layers, 1 full rope, 2 query "
            "heads a key-value head, normed queries and keys (XLA: no Mosaic "
            "backend), rotary (XLA: no Mosaic backend)")
        assert warm["moe_layout"] == (
            "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, x1, "
            "layers 0-0 dense 96, no exchange: 8 devices, data parallel; "
            "token-major sums: none traced (the dense lowering)")
        assert "mtp_layout" not in warm and "attn_operands" not in warm

    def the_class_also(self, cfg, flags):
        assert not isinstance(cfg, JoyAILMConfig)
        assert not (cfg.attention_gate or cfg.sandwich_norms or cfg.mup_enabled
                    or cfg.num_shared_experts or cfg.kv_lora_rank)
        assert [cfg.kind_of_layer(i) for i in range(5)] == [
            "short_conv", "full_rope", "short_conv", "short_conv",
            "short_conv"]
        assert [cfg.layer_is_dense(i) for i in range(5)] == (
            [True] + [False] * 4)
        assert "conv_kernel" in flags and "conv_bias" not in flags
        assert set(Lfm2MoeLMConfig.no_flag) - set(AfmoeLMConfig.no_flag) == {
            "conv_bias"}
        # the kinds: a short convolution needs a class that states its length;
        # ``full_rope`` is any class's (grouped heads here, latent attention
        # where the class states ``kv_lora_rank``)
        with pytest.raises(ValueError, match="short_conv"):
            SparseLMConfig(layer_kinds=("short_conv",)).validate()
        SparseLMConfig(layer_kinds=("full_rope",)).validate()
        with pytest.raises(ValueError, match="no bias"):
            dataclasses.replace(cfg, conv_bias=True).validate()
        # the parents still state an untied head
        for parent in (SparseLMConfig, AfmoeLMConfig):
            with pytest.raises(ValueError, match="untied head"):
                parent(tied_embeddings=True).validate()
        dataclasses.replace(cfg, tied_embeddings=False).validate()


def test_the_short_convolution_is_causal_and_a_plain_depthwise_convolution():
    """``short_conv_mix`` on ``in_proj``'s output: a change at token t
    moves nothing before t and something at t, t + 1 and t + 2 (three
    taps); ``C * conv(B * u)`` with ``lax.conv_general_dilated`` as the
    depthwise convolution (feature_group_count = lanes, K - 1 noughts in
    front), the yardstick's written-out sum, and a fourth tap alike."""
    d, t = 32, 12
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    bcu = jax.random.normal(keys[0], (2, t, 3 * d))
    for k in (3, 4, 1):
        taps = jax.random.normal(keys[1], (k, d))
        got = sparse_lm.short_conv_mix(bcu, taps)
        gate_in, gate_out, u = jnp.split(bcu, 3, axis=-1)
        conv = jax.lax.conv_general_dilated(
            gate_in * u, taps[:, None, :], window_strides=(1,),
            padding=[(k - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=d, precision="highest")
        np.testing.assert_allclose(got, gate_out * conv, atol=1e-5)
    taps = jax.random.normal(keys[1], (3, d))
    got = sparse_lm.short_conv_mix(bcu, taps)
    at = 5
    moved = sparse_lm.short_conv_mix(
        bcu.at[:, at, 2 * d:].add(1.0), taps)             # u of token 5
    changed = np.abs(np.asarray(moved - got)).max(axis=(0, 2))
    assert not changed[:at].any() and not changed[at + 3:].any()
    assert (changed[at:at + 3] > 1e-3).all()
    # position 0 sees itself alone, through the last tap
    np.testing.assert_allclose(
        got[:, 0], bcu[:, 0, d:2 * d] * taps[2]
        * bcu[:, 0, :d] * bcu[:, 0, 2 * d:], atol=1e-6)
    # the module against the yardstick's operator, the same leaves
    cfg = Lfm2MoeLMConfig(**TINY)
    mod = sparse_lm.ShortConv(cfg, name="conv")
    a = jax.random.normal(keys[2], (2, t, cfg.hidden_size))
    params = jax.jit(mod.init)(jax.random.PRNGKey(1), a)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            mod.apply(params, a), Y.short_conv(a, params["params"]),
            atol=2e-5)


@pytest.mark.parametrize("tokens, heads, kv_heads, window, split", [
    (256, 8, 2, None, False),       # group 4: the preset's
    (256, 4, 4, None, False),       # group 1: a head reads its own half
    (200, 8, 2, None, False),       # a length that pads
    (3 * 128 + 7, 8, 2, None, True),    # dq + dk/dv kernels
    (200, 4, 4, 100, True),         # ... group 1, inside a window
    (384, 6, 2, 130, False),        # group 3: a query tile over both heads
])
def test_the_kernels_for_two_heads_a_tile_are_the_dense_lowering(
        tokens, heads, kv_heads, window, split, monkeypatch):
    """Forward values and every cotangent of ``causal_attention`` at
    ``head_dim`` 64, interpreted, against ``dense_causal_attention``: query
    head ``h`` reads key-value head ``h // group`` whichever half of its
    lane tile either sits in; the one-kernel backward and, with the VMEM
    the call may ask for shrunk, the two kernels."""
    called = []
    plain = kernels._call
    monkeypatch.setattr(kernels, "_call", lambda kernel, *a, **kw: (
        called.append(kernel.__name__), plain(kernel, *a, **kw))[1])
    if split:
        monkeypatch.setattr(kernels, "VMEM_LIMIT_BYTES", 256 * 1024)
    keys = jax.random.split(jax.random.PRNGKey(tokens + heads), 4)
    q, w = (jax.random.normal(k, (2, tokens, heads * 64)) for k in keys[:2])
    k, v = (jax.random.normal(k, (2, tokens, kv_heads * 64))
            for k in keys[2:])
    assert kernels.blockwise_fits(q.shape[2], k.shape[2], 64) is None
    with jax.default_matmul_precision("highest"):
        blockwise = lambda *a: kernels.causal_attention(*a, window, 128,
                                                        True, 64)
        dense = lambda *a: sparse_lm.dense_causal_attention(*a, window, 64)
        # jitted: eagerly every operation of the four is a compile
        out, grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(blockwise(*a) * w), argnums=range(3)))(q, k, v)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(dense(*a) * w), argnums=range(3)))(q, k, v)
        np.testing.assert_allclose(jax.jit(blockwise)(q, k, v),
                                   jax.jit(dense)(q, k, v), atol=2e-5)
    assert float(out) == pytest.approx(float(want), rel=1e-5)
    for name, g, r in zip("qkv", grads, want_grads):
        assert g.shape == r.shape and rel_l2(g, r) < 2e-6, name
    assert set(called) == {"_halves_fwd_kernel", *(
        ("_halves_dq_kernel", "_halves_dkv_kernel") if split
        else ("_halves_bwd_kernel",))}


def test_blockwise_fits_says_why_not():
    fits = kernels.blockwise_fits
    assert fits(32 * 64, 8 * 64, 64) is None            # the preset's
    assert fits(28 * 128, 4 * 128, 128) is None
    assert fits(4 * 64, 2 * 64, 64) is None
    assert fits(4 * 32, 4 * 32, 32) == (
        "head_dim 32 is neither one 128-lane tile nor half of one")
    assert "neither one 128-lane tile" in fits(4 * 16, 2 * 16, 16)
    # a key-value tile is two whole heads
    assert fits(6 * 64, 3 * 64, 64) == "384 query lanes over 192 key-value lanes"
    assert fits(3 * 64, 2 * 64, 64) == "192 query lanes over 128 key-value lanes"
    # 2 x 65 heads a step have no statistics lane each
    assert fits(130 * 64, 2 * 64, 64) == (
        "more query heads a group than statistics lanes")
    assert kernels.fused_backward_fits(8192, 4, 2) is None


def test_the_tied_tables_gradient_is_the_sum_of_both_uses():
    """The table's gradient from the tied model equals the embedding's
    plus the transposed head's of the same model untied at the same
    numbers (``lm_head`` = the table's transpose); LAMB sees one leaf."""
    cfg = Lfm2MoeLMConfig(**TINY)
    params, (text, image) = fam.params(cfg), batch(cfg)
    (loss, _), grads = fam.system(cfg, params, text, image)
    untied = dataclasses.replace(cfg, tied_embeddings=False)
    table = params["params"]["token_emb"]
    both = {"params": dict(params["params"], lm_head=table.T)}
    (other, _), apart = fam.system(untied, both, text, image)
    assert float(other) == pytest.approx(float(loss), rel=1e-6)
    g = apart["params"]
    assert rel_l2(grads["params"]["token_emb"],
                  g["token_emb"] + g["lm_head"].T) < 1e-5
    assert float(jnp.linalg.norm(g["lm_head"])) > 0.1 * float(
        jnp.linalg.norm(g["token_emb"]))
    assert "lm_head" not in params["params"]
    assert sum("token_emb" in name for name in fam.leaves(params)) == 1

