"""``Lfm2MoeLMConfig`` (preset ``lfm2moe``) through models/sparse_lm.py at
a tiny size, seeded random weights, f32: loss and every gradient leaf
against the plain reference of its yardstick under both lowerings; the
short convolution is causal and is a plain depthwise convolution; the
kernels for two 64-wide heads a lane tile against the dense lowering; the
tied table gets the sum of both uses' gradients; a mechanism left out is
told; the shares add up to the uncut layer; the preset trains through the
peer's normal path and the entry points that decode refuse it."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.cli import run_aux_peer, run_inference, run_server, run_trainer
from dalle_tpu.config import (AfmoeLMConfig, JoyAILMConfig, Lfm2MoeLMConfig,
                              SparseLMConfig, lfm2moe_model_config)
from dalle_tpu.models import attention, family, sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped

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


@pytest.mark.parametrize("with_kernels", [False, True])
def test_loss_and_every_gradient_leaf_against_the_yardstick(
        with_kernels, monkeypatch, lowering_record):
    """The whole tiny model; with ``with_kernels`` the attention on two
    64-wide heads a lane tile, the grouped products and the token-major
    sums run their Pallas kernels, interpreted. Limits: f32 on both sides,
    the reference at the highest matmul precision; the two order their
    sums differently (blockwise softmax, streamed head, sorted experts,
    the taps as shifts), which the other configurations' tests read at
    the same 2e-6 / 2e-5."""
    cfg = Lfm2MoeLMConfig(**dict(TINY, **(KERNEL_WIDTHS if with_kernels
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
    tree = params["params"]
    # one table: no head leaf beside it
    assert set(tree) == {"token_emb", "final_norm", "layer_0", "layer_1",
                         "layer_2"}
    assert set(tree["layer_0"]) == {"conv", "attn_norm", "ff", "ff_norm"}
    assert set(tree["layer_0"]["conv"]) == {"in_proj", "taps", "out_proj"}
    assert set(tree["layer_0"]["ff"]) == {"dense"}
    assert set(tree["layer_1"]) == {"attn", "attn_norm", "ff", "ff_norm"}
    assert set(tree["layer_1"]["attn"]) == {"q", "k", "v", "out", "q_norm",
                                            "k_norm"}
    assert set(tree["layer_2"]["ff"]) == {"router", "router_bias", "experts"}
    d = cfg.hidden_size
    assert tree["layer_2"]["conv"]["taps"].shape == (3, d)
    assert tree["layer_2"]["conv"]["in_proj"]["kernel"].shape == (d, 3 * d)
    assert tree["layer_1"]["attn"]["q_norm"].shape == (cfg.head_dim,)
    # the taps and the table have gradients; the bias has none, either side
    for layer in ("layer_0", "layer_2"):
        assert float(jnp.abs(grads["params"][layer]["conv"]["taps"]).min()) \
            > 0
    assert float(jnp.linalg.norm(grads["params"]["token_emb"])) > 0
    for layer in ("layer_1", "layer_2"):
        for side in (grads, ref_grads):
            bias = side["params"][layer]["ff"]["router_bias"]
            assert bias.shape == (8,) and not np.asarray(bias).any()
    assert float(aux["moe_dropped"]) == 0.0
    assert float(aux["moe_dense_calls"]) == (0.0 if with_kernels else 2.0)
    # which lowering the attention layer took, asked of the record
    shut = None if with_kernels else "no Mosaic backend"
    call = "full_rope attention", (44, 4 * cfg.head_dim, 2 * cfg.head_dim)
    assert lowering_record.why_not(*call) == shut
    if with_kernels:
        # two 64-wide heads a lane tile: whole tiles (``sub`` the tile)
        assert lowering_record.recorded(*call) == {
            "why_not": None, "split_backward": None,
            "band": kernels.band_account(1, 512, None, 512)}
    # the head pass of 64-wide heads stays XLA code, for the rule's reason
    assert lowering_record.first_refusal(
        ("head norm + rotary", (44, heads * cfg.head_dim, cfg.head_dim))
        for heads in (4, 2)) == (
            "head_dim 64 is not whole 128-lane tiles" if with_kernels
            else shut)
    # the sentences, whole, as the operator reads them
    said = sparse_lm.engagement_records(cfg)
    assert said["attn_layout"] == (
        "blockwise 512: 1 of 1 attention layers, 1 full rope, 2 heads of 64 "
        "a lane tile, 2 query heads a key-value head, backward: one kernel a "
        "tile (1 of 1 layers), normed queries and keys (XLA: head_dim 64 is "
        "not whole 128-lane tiles), rotary (XLA: head_dim 64 is not whole "
        "128-lane tiles)" if with_kernels else
        "blockwise 512: 0 of 1 attention layers, 1 full rope, 2 query heads "
        "a key-value head, normed queries and keys (XLA: no Mosaic backend), "
        "rotary (XLA: no Mosaic backend)")
    assert said.get("attn_band") == (
        "1 full_rope: 1 tile, 1 at an edge whole, visited over allowed "
        "pairs 1.9961" if with_kernels else None)
    assert said["conv_layout"] == (
        f"gated short convolution: 2 of 3 layers, 3 taps, causal, depthwise "
        f"over {d} lanes; conv/mix is XLA code: B, C and u read as column "
        f"blocks of in_proj's (B, T, {3 * d}) output in place, the taps as 2 "
        "shifts along the tokens in f32 (no Mosaic kernel)")
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
    params = mod.init(jax.random.PRNGKey(1), a)
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
        out, grads = jax.value_and_grad(lambda *a: jnp.sum(
            kernels.causal_attention(*a, window, 128, True, 64) * w),
            argnums=range(3))(q, k, v)
        want, want_grads = jax.value_and_grad(lambda *a: jnp.sum(
            sparse_lm.dense_causal_attention(*a, window, 64) * w),
            argnums=range(3))(q, k, v)
        np.testing.assert_allclose(
            kernels.causal_attention(q, k, v, window, 128, True, 64),
            sparse_lm.dense_causal_attention(q, k, v, window, 64),
            atol=2e-5)
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
    params = _params(cfg)
    text, image = _batch(cfg)
    (loss, _), grads = _system(cfg, params, text, image)
    untied = dataclasses.replace(cfg, tied_embeddings=False)
    table = params["params"]["token_emb"]
    both = {"params": dict(params["params"], lm_head=table.T)}
    (other, _), apart = _system(untied, both, text, image)
    assert float(other) == pytest.approx(float(loss), rel=1e-6)
    g = apart["params"]
    assert rel_l2(grads["params"]["token_emb"],
                  g["token_emb"] + g["lm_head"].T) < 1e-5
    assert float(jnp.linalg.norm(g["lm_head"])) > 0.1 * float(
        jnp.linalg.norm(g["token_emb"]))
    assert "lm_head" not in params["params"]
    assert sum("token_emb" in jax.tree_util.keystr(path) for path, _ in
               jax.tree_util.tree_flatten_with_path(params)[0]) == 1


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
    "the head norms of queries and keys":
        lambda monkeypatch, model: dict(model, qk_norm=False),
    "the rotary":
        lambda monkeypatch, model: dict(
            model, layer_kinds=["short_conv", "full_nope", "short_conv"]),
    "tying": lambda monkeypatch, model: dict(model, tied_embeddings=False),
    "the gate B": _no_gate(0),
    "the gate C": _no_gate(1),
    "a tap": _a_tap_left_out,
    "causality of the taps": _not_causal,
    "the convolution (attention in its place)":
        lambda monkeypatch, model: dict(model, layer_kinds=["full_rope"]),
}


@pytest.fixture(scope="module")
def with_everything():
    cfg = Lfm2MoeLMConfig(**TINY)
    params = _params(cfg)
    # an ``lm_head`` and an ``attn`` for the references that read one
    d, extra = cfg.hidden_size, jax.random.split(jax.random.PRNGKey(9), 3)
    tree = dict(params["params"],
                lm_head=0.5 * jax.random.normal(extra[0],
                                                (d, cfg.vocab_size)))
    for i in (0, 2):
        tree[f"layer_{i}"] = dict(
            tree[f"layer_{i}"], attn=params["params"]["layer_1"]["attn"])
    text, image = _batch(cfg)
    (loss, _), _ = _system(cfg, params, text, image)
    return cfg, {"params": tree}, text, image, float(loss)


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


@pytest.mark.parametrize("with_kernels", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(with_kernels,
                                                   monkeypatch):
    """The preset's deployment at a small width: 32 experts, top 4, over 4
    shares of 8 consecutive experts (``expert_offset`` 0, 8, 16, 24).
    Every share's layer returns its routed part and nothing else (no
    shared expert to count once): the four summed as they come equal the
    reference's uncut layer, and every assignment is computed by one."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", with_kernels)
    base = Lfm2MoeLMConfig(**dict(
        TINY, **(KERNEL_WIDTHS if with_kernels else {}), num_experts=32,
        experts_per_token=4, experts_held=8, expert_offset=0))
    n, held = base.num_experts, base.experts_held
    assert n // held == 4
    rng = jax.random.split(jax.random.PRNGKey(3), 6)
    d, f = base.hidden_size, base.expert_width
    m = jax.random.normal(rng[0], (2, 28, d))
    whole = {"router": jax.random.normal(rng[1], (d, n)),
             "router_bias": 0.05 * jax.random.normal(rng[2], (n,)),
             "experts": {"gate": jax.random.normal(rng[3], (n, d, f)) * 0.2,
                         "up": jax.random.normal(rng[4], (n, d, f)) * 0.2,
                         "down": jax.random.normal(rng[5], (n, f, d)) * 0.2}}
    with jax.default_matmul_precision("highest"):
        want = Y.whole_layer_experts(m, whole, as_file(base))
        total, here = jnp.zeros_like(m), 0.0
        for share in range(4):
            cfg = dataclasses.replace(base, expert_offset=held * share)
            layer = sparse_lm.ExpertLayer(cfg)
            mine = {"params": dict(whole, experts={
                k: w[held * share: held * (share + 1)]
                for k, w in whole["experts"].items()})}
            idx, p = layer.apply(mine, m, method="route")   # alike on all
            y, counters = layer.apply(mine, m, idx, p)
            assert float(jnp.abs(y).max()) > 0.01
            total = total + y
            here += float(counters["here"])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert here == pytest.approx(1.0)     # every assignment, by one share
    np.testing.assert_allclose(jnp.sum(p, -1), 1.0, atol=1e-6)  # x 1.0


def test_the_expert_block_on_the_tile_is_the_same_model_to_the_last_bit(
        monkeypatch, lowering_record):
    """The preset at the widths its kernels take, f32, interpreted: loss,
    counters and every gradient leaf with the expert block's tile work in
    its kernels equal the three products a direction with XLA code between
    them (the predicate's refusal: the limit shrunk), and the step's
    ``moe_tiles_active_pct`` is the plan's: 88 tokens a call, top 2 of 8
    with 4 held, send each held expert under a tile of rows, so 4 of the
    buffer's 1 + 4 tiles hold rows in both expert layers."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = Lfm2MoeLMConfig(**dict(TINY, **KERNEL_WIDTHS))
    params, (text, image) = _params(cfg), _batch(cfg)
    said = lambda: lowering_record.recorded(
        sparse_lm.PRODUCTS_SITE, sparse_lm._block_key(
            cfg.hidden_size, cfg.expert_width, cfg.dtype))["why_not"]
    on_the_tile = _system(cfg, params, text, image)
    assert said() is None
    assert sparse_lm.engagement_records(cfg)["moe_layout"].endswith(
        "; expert block: gate, up and activation one kernel; cotangents on "
        "the tile; one dxs; inactive tiles unmoved")
    monkeypatch.setattr(grouped, "_VMEM", 256 * 1024)
    three = _system(cfg, params, text, image)
    assert said() == ("two blocks of 128 x 128 and the tiles need 2.1 MiB "
                      "of VMEM, over 0.25")
    (loss, aux), grads = on_the_tile
    assert float(aux["moe_dense_calls"]) == 0.0
    assert float(aux["moe_tiles_active_pct"]) == pytest.approx(80.0)
    assert "moe_tiles_active_pct" in sparse_lm.step_attributes(cfg)
    for a, b in zip(jax.tree.leaves(on_the_tile), jax.tree.leaves(three),
                    strict=True):
        np.testing.assert_array_equal(a, b)


TINY_FLAGS = [
    "--hidden-size", "64", "--num-hidden-layers", "3", "--layer-kinds",
    "short_conv", "full_rope", "short_conv", "--num-heads", "4",
    "--num-kv-heads", "2", "--head-dim", "16", "--expert-width", "32",
    "--num-experts", "8", "--experts-per-token", "2", "--experts-held", "4",
    "--expert-offset", "2", "--vocab-size", "96", "--text-seq-len", "28",
    "--image-grid", "4", "--vocab-text", "48", "--vocab-image", "48",
    "--dtype", "float32", "--head-chunk", "16", "--dense-width", "96"]


def test_the_preset_trains_through_the_peers_normal_path(lowering_record):
    """``run_trainer --preset lfm2moe`` (+ tiny field flags): the parser
    builds the preset's own class, TrainingTask the model its configuration
    names, and train_loop runs it with the swarm optimizer; the rows of the
    trainer's ring carry the model's records, ``conv_layout`` and
    ``head_layout`` among them; the optimizer's state has one table."""
    from dalle_tpu.obs.trace import default_tracer
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    args = run_trainer.build_parser().parse_args(
        ["--preset", "lfm2moe", *TINY_FLAGS,
         "--per-device-batch", "1", "--grad-accum-steps", "2",
         "--target-batch-size", str(1 << 30), "--seed", "7"])
    configs = run_trainer.configs_from_args(args)
    assert configs[0] == Lfm2MoeLMConfig(**TINY)
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
    assert sum("token_emb" in name for name in names) == 1
    assert not any("lm_head" in name for name in names)
    assert sum("['taps']" in name for name in names) == 2
    rows = [r for r in default_tracer().dump() if r.get("plane") == "train"]
    warm = [r for r in rows if r["phase"] == "setup/warmup"][-1]["a"]
    assert warm["conv_layout"].startswith(
        "gated short convolution: 2 of 3 layers, 3 taps, causal")
    assert "XLA code" in warm["conv_layout"]
    assert warm["head_layout"].startswith("tied: the head is the embedding")
    assert warm["attn_layout"] == (
        "blockwise 512: 0 of 1 attention layers, 1 full rope, 2 query heads "
        "a key-value head, normed queries and keys (XLA: no Mosaic backend), "
        "rotary (XLA: no Mosaic backend)")
    assert warm["moe_layout"] == (
        "4 of 8 experts held (2-5), top 2 of 8, sigmoid, bias, norm, x1, "
        "layers 0-0 dense 96, no exchange: 8 devices, data parallel; "
        "token-major sums: none traced (the dense lowering)")
    assert "mtp_layout" not in warm and "attn_operands" not in warm
    steps = [r for r in rows if r["phase"] == "loop/step"][-3:]
    for row in (r["a"] for r in steps):
        assert row["moe_dropped"] == 0.0
        assert row["moe_dense_calls"] == 2.0 * task.mesh.size
    assert task.model_cfg.optimizer_stacking()["stacked_experts"] == 4


def test_the_preset_is_a_class_of_its_own_and_the_parents_keep_theirs():
    """``benchmark/configs/{smallthinker21b,trinitymini,joyaiflash}.json``
    hold ``asdict`` of the three accepted classes: what the new class
    states as fields are class attributes there, and no key of theirs is
    new."""
    fields = lambda cls: {f.name for f in dataclasses.fields(cls)}
    assert len(fields(SparseLMConfig)) == 27
    assert len(fields(AfmoeLMConfig)) == 39
    assert len(fields(JoyAILMConfig)) == 47
    added = fields(Lfm2MoeLMConfig) - fields(AfmoeLMConfig)
    assert added == {"conv_kernel", "conv_bias"}
    for parent in (SparseLMConfig(), AfmoeLMConfig(), JoyAILMConfig()):
        assert not set(dataclasses.asdict(parent)) & added
        assert not any(getattr(parent, name) for name in added)   # off
    cfg = lfm2moe_model_config()
    assert type(cfg) is Lfm2MoeLMConfig and isinstance(cfg, AfmoeLMConfig)
    assert not isinstance(cfg, JoyAILMConfig)
    cfg.validate()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (2048, 32, 8, 64)
    assert (cfg.dense_width, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, cfg.experts_held, cfg.route_scale) == (
                7168, 1792, 32, 4, 8, 1.0)
    assert (cfg.rope_theta, cfg.rms_eps, cfg.vocab_size) == (1e6, 1e-5,
                                                             16384)
    assert (cfg.conv_kernel, cfg.conv_bias, cfg.tied_embeddings,
            cfg.qk_norm) == (3, False, True, True)
    assert not (cfg.attention_gate or cfg.sandwich_norms or cfg.mup_enabled
                or cfg.num_shared_experts or cfg.kv_lora_rank)
    assert [cfg.kind_of_layer(i) for i in range(5)] == [
        "short_conv", "full_rope", "short_conv", "short_conv", "short_conv"]
    assert [cfg.layer_is_dense(i) for i in range(5)] == [True] + [False] * 4
    flags = {a.dest for a in run_trainer.build_parser()._actions}
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


@pytest.mark.parametrize("cli, argv", [
    (run_inference, ["--checkpoint-dir", "x", "--tokenizer-path", "y",
                     "--query", "a cat"]),
    (run_server, ["--random-init"]),
    (run_aux_peer, []),
])
def test_entry_points_that_decode_refuse_the_preset_at_start(cli, argv):
    with pytest.raises(SystemExit) as refused:
        cli.main(["--preset", "lfm2moe", *argv])
    message = str(refused.value)
    assert "lfm2moe" in message and "models/decode.py" in message
    assert "short convolution" in message and "conv_kernel - 1" in message
    assert message.count(".") <= 3 and "\n" not in message   # one sentence
