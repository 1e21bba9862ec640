"""``Qwen3NextLMConfig`` (preset ``qwen3next80b``) through
models/sparse_lm.py at a tiny size, seeded random weights, f32: the family's
cases over its row (tests/sparse_family.py), and what only it has: the
chunked delta rule is the token-by-token recurrence, with its replay,
whatever the length; the triangular inverse holds where keys resemble each
other; heads of 256 lanes go through the blockwise kernels; the head pass
turns a head's first lanes alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, Qwen3NextLMConfig,
                              SparseLMConfig, qwen3next80b_model_config)
from dalle_tpu.models import attention, decode, sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from dalle_tpu.ops.pallas import delta_rule_kernels
from sparse_family import rel_l2

Y = Manifest().yardstick("qwen3next")

# one period: three gated-delta mixers and an attention layer, an expert
# block in each; a sequence (43) of two fields that is no whole number of
# chunks; half of the router's experts held; a quarter of a head rotated
TINY = dict(hidden_size=64, num_hidden_layers=4, num_heads=4, num_kv_heads=2,
            head_dim=16, expert_width=32, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, text_seq_len=27,
            image_grid=4, vocab_text=48, vocab_image=48, dtype="float32",
            head_chunk=16, linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, delta_chunk=16)
# the widths the kernels take (interpreted): 256-wide heads, two query heads
# over one key-value head; a mixer of 128-wide heads; a hidden size of one
# lane tile; a sequence of whole sublane tiles (64); one layer of each kind
# (the kernels' every shape of call: the gate's seconds are compiles)
KERNEL_WIDTHS = dict(head_dim=256, num_heads=2, num_kv_heads=1,
                     hidden_size=128, expert_width=128,
                     linear_num_key_heads=1, linear_num_value_heads=2,
                     linear_key_head_dim=128, linear_value_head_dim=128,
                     text_seq_len=48, num_hidden_layers=2,
                     layer_kinds=("gated_delta", "full_rope"))


def _patched(name, make):
    """The yardstick's function ``name`` replaced by ``make(plain)``."""
    def patch(monkeypatch, model):
        monkeypatch.setattr(Y, name, make(getattr(Y, name)))
        return model
    return patch


def _no_read_back(q, k, v, g, beta):
    """The recurrence with the delta term left out: ``S_t = e^{g_t} S_{t-1}
    + k_t (beta_t v_t)^T``, a decayed linear attention."""
    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = jnp.exp(g_t)[..., None, None] * s + k_t[:, :, None, :, None] \
            * (beta_t[..., None] * v_t)[..., None, :]
        return s, jnp.einsum("bgrkp,bgk->bgrp", s, q_t)

    start = jnp.zeros((v.shape[0], *v.shape[2:4], q.shape[-1], v.shape[-1]))
    _, o = jax.lax.scan(token, start, tuple(
        x.swapaxes(0, 1) for x in (q, k, v, g, beta)))
    return o.swapaxes(0, 1)


def _gate_then_norm(plain):
    def gated_norm(o, z, scale, eps):
        return plain(o * jax.nn.silu(z), jnp.full_like(z, 1.2784645), scale,
                     eps)         # silu(1.2784645) = 1: the norm alone
    return gated_norm


# what each mechanism is when it is left out of the REFERENCE (a key of
# ``model`` where it has one, else a patch of the yardstick's module)
LEFT_OUT = {
    "the delta term (the state read back before it is written)": _patched(
        "delta_recurrence", lambda plain: _no_read_back),
    "the decay (a state that never forgets)": _patched(
        "decay_and_beta", lambda plain: lambda b, a, gdn: (
            0.0 * plain(b, a, gdn)[0], plain(b, a, gdn)[1])),
    "beta (every write whole)": _patched(
        "decay_and_beta", lambda plain: lambda b, a, gdn: (
            plain(b, a, gdn)[0], 0.0 * b + 1.0)),
    "the L2 norms of queries and keys": _patched(
        "l2_normed", lambda plain: lambda x: x),
    "the norm before the gate (gate first, norm after)": _patched(
        "normed_then_gated", _gate_then_norm),
    "the attention's output gate": _patched(
        "output_gate", lambda plain: lambda ctx, a, attn: ctx),
    "the partial rotary (a whole head rotated)": dict(
        partial_rotary_factor=1.0),
    "the shared expert's gate": _patched(
        "shared_gate", lambda plain: lambda m, ff: 1.0 + 0.0 * plain(m, ff)),
    "the shared expert": _patched(
        "shared_part", lambda plain: lambda m, ff: 0.0 * m),
}

# the preset's deployment at a small width: 32 shares of 16 consecutive
# experts, 512 in all, top 10, beside a gated shared expert; 2 shares of 16
# where the kernels run interpreted
SHARES = {kernels_: (2 if kernels_ else 32, dict(
    {k: TINY[k] for k in ("vocab_size", "text_seq_len", "image_grid",
                          "vocab_text", "vocab_image", "dtype", "head_chunk")},
    hidden_size=128 if kernels_ else 32, expert_width=128 if kernels_ else 16,
    num_experts=32 if kernels_ else 512, experts_held=16, expert_offset=0,
    experts_per_token=10))
    for kernels_ in (False, True)}


class TestQwen3next80b(fam.Family, fam.MechanismsLeftOut, fam.SharesAddUp):
    config, preset = Qwen3NextLMConfig, "qwen3next80b"
    preset_config, Y = staticmethod(qwen3next80b_model_config), Y
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 4
    BLOCKWISE = {"full_rope": (None, 256)}
    LEFT_OUT, SHARES, EVERYTHING = LEFT_OUT, SHARES, TINY
    # f32 on both sides; the chunked rule against the token recurrence orders
    # its sums differently on top of the family's, and a head whose decay
    # leaves nothing of its state (A near 16) has a gradient of 5e-5 on
    # A_log and dt_bias, which reads 1.2e-4 from the reference (every other
    # leaf under 3e-5)
    LEAF_WITHIN = 2e-4
    ADDED = {"partial_rotary_factor", "shared_expert_gate",
             "linear_num_key_heads", "linear_num_value_heads",
             "linear_key_head_dim", "linear_value_head_dim",
             "linear_conv_kernel_dim", "delta_chunk"}
    NOT_NOUGHT_ELSEWHERE = ("partial_rotary_factor",)
    # every width is the source's
    PUBLISHED = dict(
        hidden_size=2048, num_heads=16, num_kv_heads=2, head_dim=256,
        rotary_dim=64, linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128,
        linear_conv_kernel_dim=4, linear_conv_lanes=8192,
        linear_value_lanes=4096, expert_width=512, shared_width=512,
        num_experts=512, experts_per_token=10, experts_held=16,
        rms_eps=1e-6, rope_theta=1e7, vocab_size=18992, hidden_act="silu",
        partial_rotary_factor=0.25, delta_chunk=64)
    REFUSAL, REFUSAL_STOPS = ("gated-delta-rule mixer", "'gated_delta'"), None

    @staticmethod
    def weights_with_everything(cfg):
        """Weights whose every head REMEMBERS: drawn as the source draws it
        (``A`` from U(0, 16)) a head's state is gone in a token or two and
        what the rule does to it moves an untrained model's loss by less
        than the limit; at ``A`` = 0.05 a state lasts some fifteen tokens and
        each mechanism counts."""
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.full_like(leaf, np.log(0.05))
            if "A_log" in jax.tree_util.keystr(path) else leaf,
            fam.params(cfg))

    def the_yardstick_also(self, *, cfg, tree, shut, said, grads,
                           with_kernels, lowering_record, **_):
        layers = cfg.num_hidden_layers      # 4, or 2 where the kernels run
        last, mixers = f"layer_{layers - 1}", layers - 1
        assert set(tree) == {"token_emb", "lm_head", "final_norm", *(
            f"layer_{i}" for i in range(layers))}
        for layer in (f"layer_{i}" for i in range(mixers)):
            assert set(tree[layer]) == {"attn_norm", "gdn", "ff_norm", "ff"}
            assert set(tree[layer]["gdn"]) == {
                "in_proj", "taps", "dt_bias", "A_log", "norm", "out_proj"}
        assert set(tree[last]) == {"attn_norm", "attn", "ff_norm", "ff"}
        assert set(tree[last]["attn"]) == {
            "q", "k", "v", "gate", "out", "q_norm", "k_norm"}
        assert set(tree[last]["ff"]) == {"router", "experts", "shared",
                                         "shared_gate"}
        d = cfg.hidden_size
        keys, inner = cfg.linear_key_lanes, cfg.linear_value_lanes
        heads = cfg.linear_num_value_heads
        gdn = tree["layer_0"]["gdn"]
        assert set(gdn["in_proj"]) == {"qkv", "z", "ba"}
        assert gdn["in_proj"]["qkv"]["kernel"].shape == (d, 2 * keys + inner)
        assert gdn["in_proj"]["z"]["kernel"].shape == (d, inner)
        assert gdn["in_proj"]["ba"]["kernel"].shape == (d, 2 * heads)
        assert gdn["taps"].shape == (4, 2 * keys + inner)
        assert gdn["norm"].shape == (cfg.linear_value_head_dim,)
        assert gdn["A_log"].shape == gdn["dt_bias"].shape == (heads,)
        assert tree[last]["ff"]["shared_gate"].shape == (d,)
        assert tree[last]["attn"]["q_norm"].shape == (cfg.head_dim,)
        # every leaf of the mixer and the shared gate has a gradient
        for name, leaf in grads["params"]["layer_0"]["gdn"].items():
            assert float(jnp.abs(jax.tree.leaves(leaf)[0]).max()) > 0, name
        assert float(jnp.abs(
            grads["params"][last]["ff"]["shared_gate"]).max()) > 0
        # a mixer of lane tiles takes the rule's kernels, and says so
        tokens = cfg.total_seq_len
        rule = sparse_lm.DELTA_SITE, sparse_lm._delta_key(tokens, cfg)
        assert lowering_record.why_not(*rule) == (
            None if with_kernels else shut)
        layout = said["gdn_layout"]
        assert layout.startswith(
            f"gated-delta-rule mixer: {mixers} of {layers} layers, "
            f"{cfg.linear_num_key_heads} query/key heads x "
            f"{cfg.linear_key_head_dim} serving {heads} value heads x "
            f"{cfg.linear_value_head_dim}, 4 taps with no bias over "
            f"{cfg.linear_conv_lanes} lanes; the rule in chunks of 16, "
            f"{-(-tokens // 16)} a sequence of {tokens}")
        assert "no (T, T) array and no state a token" in layout
        taps = sparse_lm.GDN_TAPS_SITE, sparse_lm._gdn_taps_key(tokens, cfg)
        if with_kernels:
            assert lowering_record.recorded(*rule) == {
                "why_not": None, "chunks_a_step": 4, "keys_a_step": 1,
                "backward": delta_rule_kernels.BACKWARD}
            assert ("gdn/rule: a Pallas kernel a direction (4 chunks of 1 "
                    "key heads a grid step, a chunk's tables, its inverse "
                    "and the carried "
                    "states in VMEM; backward: one kernel, a grid step's "
                    "chunks forward again from the state the forward kept a "
                    "step, then in reverse; a rematerialised layer keeps o, "
                    "the state a grid step, the inverses, the normalised q "
                    "and k and the g and beta rows, 0.265625 MiB a sample "
                    "and layer: its replay runs neither the forward kernel "
                    "nor the norms and rows again); taps and SiLU") in layout
            # the Mamba-2 mixer's taps pass and the head pass take the
            # mixer's shapes; the attention's heads the 256-wide form
            assert lowering_record.recorded(*taps) == {"why_not": None}
            assert "taps and SiLU: the Mamba-2 mixer's pass" in layout
            assert "the heads' norm before the gate: one pass on the lanes" \
                in layout
            assert said["attn_layout"].startswith(
                "blockwise 512: 1 of 1 attention layers, 1 full rope, one "
                "head of 256 over 2 lane tiles, 2 query heads a key-value "
                "head, backward: one kernel a tile (1 of 1 layers), normed "
                "queries and keys (one pass on the lanes: 1 of 1 layers), "
                "rotary of a head's first 64 lanes (in the head pass: 1 of 1 "
                "rope layers), gated output")
        else:
            assert (f"gdn/rule: XLA chunks ({shut}), replayed a block of 16 "
                    "chunks in the backward pass; taps") in layout
            assert f"taps and SiLU: XLA code ({shut})" in layout
            assert "rotary of a head's first 4 lanes" in said["attn_layout"]
        assert "ssm_layout" not in said and "conv_layout" not in said
        assert said["moe_layout"].startswith(
            "4 of 8 experts held (2-5), top 2 of 8, softmax over the chosen, "
            f"a shared expert of {cfg.expert_width} under a sigmoid gate a "
            "token, no exchange")

    def the_normal_path_also(self, *, names, warm, **_):
        assert sum("['gdn']['A_log']" in name for name in names) == 3
        assert sum("['ff']['shared_gate']" in name for name in names) == 4
        assert warm["gdn_layout"].startswith(
            "gated-delta-rule mixer: 3 of 4 layers, 2 query/key heads x 8 "
            "serving 4 value heads x 8")
        assert "gdn/rule: XLA chunks (no Mosaic backend)" in warm[
            "gdn_layout"]
        assert warm["layer_loop"] == (
            "unrolled: 4 layers, each rematerialised but its attention")
        assert warm["attn_layout"].startswith(
            "blockwise 512: 0 of 1 attention layers, 1 full rope")
        assert warm["moe_layout"].startswith(
            "4 of 8 experts held (2-5), top 2 of 8, softmax over the chosen, "
            "a shared expert of 32 under a sigmoid gate a token, no "
            "exchange: 8 devices, data parallel")
        assert "ssm_layout" not in warm and "mtp_layout" not in warm

    def the_class_also(self, cfg, flags):
        for parent in fam.CHAIN:
            if parent is not Qwen3NextLMConfig:
                assert parent().partial_rotary_factor == 1.0
                assert parent().rotary_dim == parent().head_dim
                assert not (parent().shared_expert_gate
                            or parent().linear_num_value_heads
                            or parent().delta_chunk)
        assert cfg.attention_gate and cfg.qk_norm and cfg.shared_expert_gate
        assert cfg.router_softmax_over_chosen and cfg.score_func == "softmax"
        assert not (cfg.selection_bias or cfg.route_norm or cfg.mup_enabled
                    or cfg.sandwich_norms or cfg.num_dense_layers
                    or cfg.tied_embeddings or cfg.kv_lora_rank)
        assert [cfg.kind_of_layer(i) for i in range(4)] == [
            "gated_delta", "gated_delta", "gated_delta", "full_rope"]
        assert {"linear_num_value_heads", "delta_chunk",
                "partial_rotary_factor"} <= flags
        assert "shared_expert_gate" not in flags
        # init: dt_bias ones, A_log the log of U(0, 16)
        drawn = sparse_lm.init_params(sparse_lm.build(self.tiny()),
                                      jax.random.PRNGKey(0))["params"]
        gdn = drawn["layer_0"]["gdn"]
        assert np.all(np.asarray(gdn["dt_bias"]) == 1.0)
        assert np.all(np.exp(np.asarray(gdn["A_log"])) <= 16.0)
        # the kinds: each needs a class that states it
        with pytest.raises(ValueError, match="gated-delta-rule mixer"):
            SparseLMConfig(layer_kinds=("gated_delta",)).validate()
        with pytest.raises(ValueError, match="gated-delta-rule mixer"):
            AfmoeLMConfig(layer_kinds=("gated_delta",)).validate()
        with pytest.raises(ValueError, match="'gated_delta' or 'full_rope'"):
            dataclasses.replace(cfg, layer_kinds=("window_rope",)).validate()
        with pytest.raises(ValueError, match="power of two"):
            dataclasses.replace(cfg, delta_chunk=48).validate()
        with pytest.raises(ValueError, match="multiple of"):
            dataclasses.replace(cfg, linear_num_key_heads=5).validate()
        with pytest.raises(ValueError, match="even number"):
            dataclasses.replace(cfg, partial_rotary_factor=0.3).validate()
        with pytest.raises(ValueError, match="gated shared expert"):
            dataclasses.replace(cfg, num_shared_experts=0).validate()


def test_the_tiny_mixer_says_its_refusal_by_shape(monkeypatch,
                                                  lowering_record):
    """With a Mosaic backend the tiny model's rule (43 tokens in chunks of
    16, heads of 8 lanes) is the XLA lowering, refused by what the site
    observed of the shapes; ``gdn_layout`` says which."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = Qwen3NextLMConfig(**TINY)
    (q, k, v, g, beta), _ = _rule_operands(cfg.total_seq_len)
    got = sparse_lm.delta_rule(q, k, v, g, beta, mesh=None, cfg=cfg)
    np.testing.assert_array_equal(got, sparse_lm.chunked_delta_rule(
        q, k, v, g, beta, key_heads=2, chunk=16))
    why = "43 tokens are not whole chunks of 16"
    assert lowering_record.why_not(
        sparse_lm.DELTA_SITE, sparse_lm._delta_key(43, cfg)) == why
    assert (f"gdn/rule: XLA chunks ({why}), replayed a block of 16 chunks "
            "in the backward pass") in sparse_lm.gdn_layout(cfg)
    # whole chunks of it: the heads' lanes
    sparse_lm.delta_rule(*(x[:, :32] for x in (q, k, v, g, beta)),
                         mesh=None, cfg=cfg)
    assert lowering_record.why_not(
        sparse_lm.DELTA_SITE, sparse_lm._delta_key(32, cfg)) == (
            "heads of 8 and 8 lanes are not whole 128-lane tiles")


def _rule_operands(tokens, g_heads=2, r=2, dk=8, dv=8):
    keys = jax.random.split(jax.random.PRNGKey(tokens), 6)
    h = g_heads * r
    q = jax.random.normal(keys[0], (2, tokens, g_heads * dk))
    k = jax.random.normal(keys[1], (2, tokens, g_heads * dk))
    v = jax.random.normal(keys[2], (2, tokens, h * dv))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (2, tokens, h)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (2, tokens, h)))
    return (sparse_lm.l2_normed(q, dk, dk ** -0.5), sparse_lm.l2_normed(k, dk),
            v, g, beta), jax.random.normal(keys[5], v.shape)


@pytest.mark.parametrize("tokens, chunk", [
    (43, 16),       # no whole number of chunks: padded behind
    (5, 16),        # shorter than a chunk
    (64, 16),       # many
    (128, 64),      # the preset's chunk
    (100, 64),      # ... and a length that is no multiple of it
])
def test_the_chunked_rule_is_the_token_recurrence(tokens, chunk):
    """:func:`chunked_delta_rule` against the yardstick's ``lax.scan`` over
    the tokens: the result, and every operand's gradient through a
    rematerialised call (the replay), f32."""
    g_heads, r, dk, dv = 2, 2, 8, 8
    operands, w = _rule_operands(tokens)

    def chunked(*o):
        y = jax.checkpoint(lambda *o: sparse_lm.chunked_delta_rule(
            *o, key_heads=g_heads, chunk=chunk))(*o)
        return jnp.sum(y * w), y

    def by_token(q, k, v, g, beta):
        heads = lambda x, d: x.reshape(2, tokens, g_heads, d)
        y = Y.delta_recurrence(
            heads(q, dk), heads(k, dk), v.reshape(2, tokens, g_heads, r, dv),
            g.reshape(2, tokens, g_heads, r),
            beta.reshape(2, tokens, g_heads, r)).reshape(v.shape)
        return jnp.sum(y * w), y

    with jax.default_matmul_precision("highest"):
        (_, got), grads = jax.jit(jax.value_and_grad(
            chunked, range(5), has_aux=True))(*operands)
        (_, want), ref = jax.jit(jax.value_and_grad(
            by_token, range(5), has_aux=True))(*operands)
    assert got.shape == operands[2].shape
    assert rel_l2(got, want) < 2e-6
    for name, g_, r_ in zip("q k v g beta".split(), grads, ref):
        assert rel_l2(g_, r_) < 1e-5, name


def test_the_inverse_holds_where_keys_resemble_each_other():
    """``unit_lower_inverse`` against a triangular solve in float64's stead
    (numpy, f64) on the matrices of keys that all point one way, beta 1 and
    no decay: the entries of A near 1, where the plain product of six
    factors over the whole chunk loses the answer in f32."""
    c = 64
    rng = np.random.default_rng(0)
    k = rng.normal(size=(3, c, 8)) * 0.05 + 1.0
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    a = np.tril(k @ k.transpose(0, 2, 1), -1)
    assert a[a != 0].min() > 0.95
    want = np.linalg.inv(np.eye(c) + a)
    got = jax.jit(sparse_lm.unit_lower_inverse)(jnp.asarray(a, jnp.float32))
    assert rel_l2(got, want) < 1e-5
    # the product over the whole chunk, in f32 as the rule would run it
    power = -jnp.asarray(a, jnp.float32)
    plain = jnp.eye(c) + power
    for _ in range(5):
        power = jnp.matmul(power, power, precision="highest")
        plain = jnp.matmul(plain, jnp.eye(c) + power, precision="highest")
    assert not rel_l2(plain, want) < 1e-2
    # chunks of 16 and of 8 are the base alone
    for size in (16, 8, 2, 1):
        small = jnp.asarray(a[:, :size, :size], jnp.float32)
        assert rel_l2(sparse_lm.unit_lower_inverse(small),
                      np.linalg.inv(np.eye(size) + a[:, :size, :size])) < 1e-5


@pytest.mark.parametrize("tokens, group, vmem", [
    (600, 2, None),             # one kernel a tile in the backward
    (1100, 4, None),
    (1100, 2, 4 * 1024 * 1024),  # dq and dk/dv kernels
])
def test_heads_of_256_go_through_the_blockwise_kernels(tokens, group, vmem,
                                                       monkeypatch):
    """The kernels' third form, interpreted, against
    ``dense_causal_attention``: the context and every cotangent, with the
    backward as one kernel and as two (the limit shrunk)."""
    if vmem:
        monkeypatch.setattr(kernels, "VMEM_LIMIT_BYTES", vmem)
    hd = kernels.WIDE
    assert (kernels.fused_backward_fits(tokens, group, 4, lanes=hd)
            is None) == (vmem is None)
    assert kernels.blockwise_fits(group * hd, hd, hd) is None
    keys = jax.random.split(jax.random.PRNGKey(tokens), 4)
    q = jax.random.normal(keys[0], (1, tokens, group * hd))
    k = jax.random.normal(keys[1], (1, tokens, hd))
    v = jax.random.normal(keys[2], (1, tokens, hd))
    w = jax.random.normal(keys[3], q.shape)

    def out(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), (0, 1, 2)))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = out(lambda q, k, v: kernels.causal_attention(
            q, k, v, None, kernels.BLOCK, True, hd))
        want = out(lambda q, k, v: sparse_lm.dense_causal_attention(
            q, k, v, None, hd))
    # a sum of signed terms: the arrays below are the comparison
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for name, g_, r_ in zip("q k v".split(), got[1], want[1]):
        assert rel_l2(g_, r_) < 1e-5, name


def test_what_the_256_wide_form_costs_and_what_is_still_refused():
    fits = kernels.blockwise_fits
    assert fits(16 * 256, 2 * 256, 256) is None            # the preset's
    assert fits(16 * 256, 3 * 128, 256) == (
        "4096 query lanes over 384 key-value lanes")
    assert fits(4 * 32, 4 * 32, 32) == (
        "head_dim 32 is neither one 128-lane tile nor half of one")
    assert "neither one 128-lane tile" in fits(4 * 512, 512, 512)
    # the one-kernel backward holds dk and dv of 8 192 tokens of 256 lanes
    assert kernels.fused_backward_fits(8192, 8, 2, lanes=256) is None
    assert "dk and dv of 16384 tokens" in kernels.fused_backward_fits(
        16384, 8, 2, lanes=256)
    assert kernels.fused_backward_fits(16384, 8, 2) is None    # 128 wide
    # edge tiles are cut as the 128-wide kernels cut them
    assert kernels.sub_block(kernels.BLOCK, 256) == kernels.SUB_BLOCK
    assert kernels.band_of(8192, None, 256) == kernels.band_of(8192, None)


@pytest.mark.parametrize("norm", [True, False])
def test_the_head_pass_turns_a_heads_first_lanes_alone(norm, monkeypatch):
    """The pass's kernel (interpreted), its XLA lowering and the yardstick's
    partial rotary on heads of 256 lanes of which 64 are rotated: the
    result and the cotangents; the other 192 lanes pass as they are."""
    hd, turned, theta = 256, 64, 1e7
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3 * hd))
    scale = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (hd,))
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def run(interpret):
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
        def f(x, scale):
            y = sparse_lm.head_pass(x, scale if norm else None, mesh=None,
                                    eps=1e-6, head_dim=hd, theta=theta,
                                    turned=turned)
            return jnp.sum(y * w), y
        return jax.jit(jax.value_and_grad(f, (0, 1) if norm else (0,),
                                          has_aux=True))(x, scale)

    (_, got), grads = run(True)
    (_, want), ref = run(False)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for g_, r_ in zip(grads, ref):
        assert rel_l2(g_, r_) < 1e-5
    heads = x.reshape(2, 24, 3, hd)
    if norm:
        heads = Y._rms_norm(heads, scale, 1e-6)
    theirs = Y.partial_rotary(heads, theta, turned)
    np.testing.assert_allclose(got.reshape(theirs.shape), theirs, atol=5e-6)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(theirs.shape)[..., turned:]),
        np.asarray(heads[..., turned:]))
    assert float(jnp.abs(got.reshape(theirs.shape)[:, 1:, :, :turned]
                         - heads[:, 1:, :, :turned]).max()) > 0.1


def test_decode_refuses_the_kind_by_name():
    decode.refuse_recurrent_layers(SparseLMConfig())        # no such layer
    with pytest.raises(NotImplementedError, match="'gated_delta'"):
        decode.refuse_recurrent_layers(Qwen3NextLMConfig())
    decode.refuse_selected_layers(Qwen3NextLMConfig())      # not its kind

