"""``NemotronHLMConfig`` (preset ``twotower30b``) through models/sparse_lm.py
at a tiny size, seeded random weights, f32: loss and every gradient leaf
against the plain reference of its yardstick under both lowerings; the
chunked scan is the token-by-token recurrence, with its replay, whatever
the length; the taps are a plain causal depthwise convolution; a mechanism
left out is told; a one-part layer has exactly its part's leaves; experts
of two products whose width ends in half a lane tile go through the grouped
kernels as they are; the preset trains through the peer's normal path and
the entry points that decode refuse it. (The shares' sum is a case of
``test_trinity_model.py``'s test, group 16 cases of
``test_smallthinker_attention.py``'s.)"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.cli import run_aux_peer, run_inference, run_server, run_trainer
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, KeyeLMConfig,
                              Lfm2MoeLMConfig, NemotronHLMConfig,
                              SparseLMConfig, twotower30b_model_config)
from dalle_tpu.models import attention, decode, family, sparse_lm
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped

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
    """Seeded weights with every vector leaf (norm scales, the biases, the
    mixer's ``dt_bias``, ``A_log`` and ``D``) moved off its initial value,
    so that each counts."""
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


@pytest.mark.parametrize("with_kernels", [False, True])
def test_loss_and_every_gradient_leaf_against_the_yardstick(
        with_kernels, monkeypatch, lowering_record):
    """The whole tiny model; with ``with_kernels`` the attention, the
    grouped products of the two-product experts (width 192 = 1.5 lane
    tiles) and the token-major sums run their Pallas kernels, interpreted.
    Limits: f32 on both sides, the reference at the highest matmul
    precision; the two order their sums differently (the chunked scan
    against the token recurrence, blockwise softmax, streamed head, sorted
    experts), which the other configurations' tests read at the same 2e-6 /
    2e-5."""
    cfg = NemotronHLMConfig(**dict(TINY, **(KERNEL_WIDTHS if with_kernels
                                            else {})))
    cfg.validate()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", with_kernels)
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, aux), grads = _system(cfg, params, text, image)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image, as_file(cfg))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5, jax.tree_util.keystr(path)
    # a one-part layer has its one norm and its part's leaves, no others
    tree = params["params"]
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
    # every leaf of the mixer has a gradient; the bias has none, either side
    for name, leaf in grads["params"]["layer_0"]["ssm"].items():
        assert float(jnp.abs(jax.tree.leaves(leaf)[0]).max()) > 0, name
    for layer in ("layer_1", "layer_3"):
        for side in (grads, ref_grads):
            bias = side["params"][layer]["ff"]["router_bias"]
            assert bias.shape == (8,) and not np.asarray(bias).any()
    # the step's counters are the two expert layers'
    assert float(aux["moe_dropped"]) == 0.0
    assert float(aux["moe_dense_calls"]) == (0.0 if with_kernels else 2.0)
    assert 0 < float(aux["moe_assignments_here_pct"]) < 100
    # which lowering each site took, asked of the record
    shut = None if with_kernels else "no Mosaic backend"
    call = "full_nope attention", (43, cfg.num_heads * cfg.head_dim,
                                   cfg.num_kv_heads * cfg.head_dim)
    assert lowering_record.why_not(*call) == shut
    scan = sparse_lm.SCAN_SITE, sparse_lm._scan_key(43, cfg)
    assert scan[1] == (43, 4, 8, 2, 16, 8)
    # the tiny mixer's shapes are none the scan's kernels take
    refusal = "43 tokens are not whole chunks of 8"
    assert lowering_record.why_not(*scan) == (
        refusal if with_kernels else shut)
    said = sparse_lm.engagement_records(cfg)
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
        "one part a layer behind one norm: mamba2 experts full_nope experts")
    assert said["attn_layout"].startswith(
        f"blockwise 512: {int(with_kernels)} of 1 attention layers, 1 full "
        "no-rope + 0 window 0 rope, 2 query heads a key-value head")
    assert "a shared expert of " + str(cfg.shared_expert_width) \
        in said["moe_layout"]
    if with_kernels:
        assert said["moe_layout"].endswith(
            "expert block: " + sparse_lm.UNGATED_ON_THE_TILE)
        assert sparse_lm.UNGATED_ON_THE_TILE.startswith(
            "two products an expert, not gated")


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
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, _), grads = _system(cfg, params, text, image)
    ref_loss, ref_grads = Y.loss_and_grads(params, text, image, as_file(cfg))
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5, jax.tree_util.keystr(path)
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
    "the scale 2.5": lambda monkeypatch, model: dict(model, route_scale=1.0),
    "the decay (a state that never forgets)": _patched(
        "mamba2", lambda plain: lambda a, ssm, model: plain(
            a, dict(ssm, A_log=ssm["A_log"] - 30.0), model)),
}


@pytest.fixture(scope="module")
def with_everything():
    # the mixer's out-projection at the other projections' scale (not the
    # source's 1 / sqrt(52) of it), so that what the mixer computes counts
    # in the loss
    cfg = NemotronHLMConfig(**dict(TINY, residual_rescale_layers=0))
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, _), _ = _system(cfg, params, text, image)
    return cfg, params, text, image, float(loss)


@pytest.mark.parametrize("mechanism", list(LEFT_OUT))
def test_a_mechanism_left_out_is_told(mechanism, with_everything,
                                      monkeypatch):
    """The system against the reference whole agrees; against the
    reference without the mechanism it does not (at least ten times the
    distance at which they agree)."""
    cfg, params, text, image, loss = with_everything
    whole, _ = jax.jit(lambda p: Y.loss_fn(p, text, image,
                                           as_file(cfg)))(params)
    assert loss == pytest.approx(float(whole), rel=2e-6)
    without = LEFT_OUT[mechanism](monkeypatch, as_file(cfg))
    lacking, _ = jax.jit(lambda p: Y.loss_fn(p, text, image,
                                             without))(params)
    assert abs(float(lacking) - loss) > 2e-5 * loss, mechanism


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


def test_the_ungated_block_on_the_tile_is_the_two_products_to_the_last_bit(
        monkeypatch, lowering_record):
    """The preset at the widths its kernels take, f32, interpreted: loss and
    every gradient leaf with ``up`` and the activation in one kernel and the
    cotangent on the tile equal the two products a direction with XLA code
    between them, which a block that does not fit VMEM runs."""
    cfg = NemotronHLMConfig(**dict(TINY, **KERNEL_WIDTHS))
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, _), grads = _system(cfg, params, text, image)
    key = sparse_lm._block_key(cfg.hidden_size, cfg.expert_width, "float32",
                               gated=False)
    assert key[-1] == "ungated"
    assert lowering_record.recorded(sparse_lm.PRODUCTS_SITE, key) == {
        "why_not": None}
    monkeypatch.setattr(grouped, "_VMEM", 64 * 1024)
    jax.clear_caches()
    (apart, _), grads_apart = _system(cfg, params, text, image)
    refusal = lowering_record.recorded(sparse_lm.PRODUCTS_SITE,
                                       key)["why_not"]
    assert refusal.startswith("a block of 128 x 192 and the tiles need ")
    assert sparse_lm._block_words(refusal, gated=False).startswith(
        "two products a direction, not gated (a block of 128 x 192")
    assert float(loss) == float(apart)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(grads_apart)):
        np.testing.assert_array_equal(g, r, jax.tree_util.keystr(path))


TINY_FLAGS = [
    "--hidden-size", "64", "--num-hidden-layers", "4", "--layer-kinds",
    "mamba2", "experts", "full_nope", "experts", "--num-heads", "4",
    "--num-kv-heads", "2", "--head-dim", "16", "--expert-width", "32",
    "--shared-expert-width", "48", "--num-experts", "8",
    "--experts-per-token", "2", "--experts-held", "4", "--expert-offset",
    "2", "--vocab-size", "96", "--text-seq-len", "27", "--image-grid", "4",
    "--vocab-text", "48", "--vocab-image", "48", "--dtype", "float32",
    "--head-chunk", "16", "--mamba-num-heads", "4", "--mamba-head-dim", "8",
    "--ssm-groups", "2", "--ssm-state-size", "16", "--ssm-chunk", "8"]


def test_the_preset_trains_through_the_peers_normal_path(lowering_record):
    """``run_trainer --preset twotower30b`` (+ tiny field flags): the parser
    builds the preset's own class, TrainingTask the model its configuration
    names, and train_loop runs it with the swarm optimizer; the rows of the
    trainer's ring carry the model's records, ``ssm_layout`` among them;
    LAMB has one trust ratio an expert on the two-leaf experts."""
    from dalle_tpu.obs.trace import default_tracer
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    args = run_trainer.build_parser().parse_args(
        ["--preset", "twotower30b", *TINY_FLAGS,
         "--per-device-batch", "1", "--grad-accum-steps", "2",
         "--target-batch-size", str(1 << 30), "--seed", "7"])
    configs = run_trainer.configs_from_args(args)
    assert configs[0] == NemotronHLMConfig(**TINY)
    task = TrainingTask(*configs)
    assert family(task.model_cfg) is sparse_lm
    assert isinstance(task.model, sparse_lm.SparseLM)
    losses = []
    with task:
        train_loop(task, max_steps=3, warmup_steps=1,
                   on_step=lambda n, loss: losses.append(loss))
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_flatten_with_path(
                     task.collab_optimizer.state.params)[0]]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert sum("['ssm']['A_log']" in name for name in names) == 1
    assert sum("['experts']['up']" in name for name in names) == 2
    assert not any("['gate']" in name for name in names)
    rows = [r for r in default_tracer().dump() if r.get("plane") == "train"]
    warm = [r for r in rows if r["phase"] == "setup/warmup"][-1]["a"]
    assert warm["ssm_layout"].startswith(
        "Mamba-2 mixer: 1 of 4 layers, 4 heads x 8")
    assert ("ssm/scan is XLA code (no Mosaic backend), its backward plain "
            "differentiation of the chunked form; taps, bias and SiLU: XLA "
            "code (no Mosaic backend); gate and group norm: XLA code (no "
            "Mosaic backend); the replay keeps") in warm["ssm_layout"]
    assert warm["layer_loop"] == (
        "unrolled: 4 layers, each rematerialised but its attention, one "
        "part a layer behind one norm: mamba2 experts full_nope experts")
    assert warm["attn_layout"].startswith(
        "blockwise 512: 0 of 1 attention layers, 1 full no-rope")
    assert warm["moe_layout"].startswith(
        "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, x2.5, "
        "a shared expert of 48, no exchange: 8 devices, data parallel")
    assert "conv_layout" not in warm and "mtp_layout" not in warm
    steps = [r for r in rows if r["phase"] == "loop/step"][-3:]
    for row in (r["a"] for r in steps):
        assert row["moe_dropped"] == 0.0
        assert row["moe_dense_calls"] == 2.0 * task.mesh.size
    assert task.model_cfg.optimizer_stacking()["stacked_experts"] == 4


def test_the_preset_is_a_class_of_its_own_and_the_parents_keep_theirs():
    """The accepted configurations' files hold ``asdict`` of their classes:
    what the new class states as fields are class attributes there, and no
    key of theirs is new."""
    fields = lambda cls: {f.name for f in dataclasses.fields(cls)}
    assert len(fields(SparseLMConfig)) == 27
    assert len(fields(AfmoeLMConfig)) == 39
    added = fields(NemotronHLMConfig) - fields(AfmoeLMConfig)
    assert added == {
        "conv_kernel", "conv_bias", "one_part_layers", "expert_gated",
        "shared_expert_width", "mamba_num_heads", "mamba_head_dim",
        "ssm_groups", "ssm_state_size", "ssm_chunk",
        "residual_rescale_layers"}
    for parent in (SparseLMConfig(), AfmoeLMConfig(), JoyAILMConfig(),
                   Lfm2MoeLMConfig(), KeyeLMConfig()):
        new = added - {"conv_kernel", "conv_bias"}     # Lfm2MoeLMConfig's
        assert not set(dataclasses.asdict(parent)) & new
        assert parent.expert_gated and not parent.one_part_layers
        assert not (parent.mamba_num_heads or parent.shared_expert_width)
    assert AfmoeLMConfig().shared_width == 1024        # 1 x expert_width
    cfg = twotower30b_model_config()
    assert type(cfg) is NemotronHLMConfig and isinstance(cfg, AfmoeLMConfig)
    cfg.validate()
    # every width is the source's
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (2688, 32, 2, 128)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_inner,
            cfg.ssm_groups, cfg.ssm_state_size, cfg.conv_kernel,
            cfg.ssm_chunk) == (64, 64, 4096, 8, 128, 4, 128)
    assert cfg.mamba_conv_lanes == 6144
    assert (cfg.expert_width, cfg.shared_width, cfg.num_experts,
            cfg.experts_per_token, cfg.experts_held, cfg.route_scale) == (
                1856, 3712, 128, 6, 8, 2.5)
    assert (cfg.rms_eps, cfg.vocab_size, cfg.hidden_act) == (
        1e-5, 16384, "relu2")
    assert cfg.conv_bias and cfg.selection_bias and cfg.route_norm
    assert not (cfg.attention_gate or cfg.qk_norm or cfg.sandwich_norms
                or cfg.mup_enabled or cfg.num_dense_layers
                or cfg.tied_embeddings or cfg.kv_lora_rank)
    assert [cfg.kind_of_layer(i) for i in range(7)] == [
        "mamba2", "experts", "mamba2", "experts", "mamba2", "full_nope",
        "experts"]
    flags = {a.dest for a in run_trainer.build_parser()._actions}
    assert {"mamba_num_heads", "ssm_chunk", "shared_expert_width"} <= flags
    assert not {"one_part_layers", "expert_gated",
                "residual_rescale_layers"} & flags
    # the source's rescale_prenorm_residual: a mixer's out-projection is
    # U(+-1 / sqrt(fan_in)) over the root of the published depth
    assert cfg.residual_rescale_layers == 52
    tiny = NemotronHLMConfig(**TINY)
    drawn = sparse_lm.init_params(sparse_lm.build(tiny),
                                  jax.random.PRNGKey(0))["params"]
    for leaf, fan_in in (
            (drawn["layer_0"]["ssm"]["out_proj"]["kernel"], 32),
            (drawn["layer_2"]["attn"]["out"]["kernel"], 64),
            (drawn["layer_1"]["ff"]["experts"]["down"], 32),
            (drawn["layer_1"]["ff"]["shared"]["down"]["kernel"], 48)):
        bound = (fan_in * 52) ** -0.5
        assert 0.9 * bound < float(jnp.abs(leaf).max()) <= bound
        assert float(leaf.std()) == pytest.approx(bound / 3 ** 0.5, rel=0.08)
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


@pytest.mark.parametrize("cli, argv", [
    (run_inference, ["--checkpoint-dir", "x", "--tokenizer-path", "y",
                     "--query", "a cat"]),
    (run_server, ["--random-init"]),
    (run_aux_peer, []),
])
def test_entry_points_that_decode_refuse_the_preset_at_start(cli, argv):
    with pytest.raises(SystemExit) as refused:
        cli.main(["--preset", "twotower30b", *argv])
    message = str(refused.value)
    assert "twotower30b" in message and "models/decode.py" in message
    assert "state-space mixer" in message and "'mamba2'" in message
    assert "\n" not in message


def test_decode_refuses_the_kind_by_name():
    decode.refuse_recurrent_layers(SparseLMConfig())        # no such layer
    with pytest.raises(NotImplementedError, match="'mamba2'"):
        decode.refuse_recurrent_layers(NemotronHLMConfig())
    decode.refuse_selected_layers(NemotronHLMConfig())      # not its kind
