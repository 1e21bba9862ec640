"""The Mamba-2 mixer's two passes (ops/pallas/ssm_pass_kernels.py),
interpreted on the CPU, against the XLA lowerings of the same sites
(``sparse_lm.causal_taps_silu`` and ``sparse_lm.gated_group_norm`` on slices
of ``in_proj``'s output): results and every gradient through a
rematerialised call, across a token tile's edge (the halo, forward and
backward), one tile, a sequence shorter than the taps, f32 and bfloat16; the
sites' predicates, each refusal with its recorded reason and the XLA
lowering's result; the mixer whole with the passes on and with both refused;
per shard on the 8-device mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import NemotronHLMConfig
from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import ssm_pass_kernels as K
from dalle_tpu.parallel.mesh import make_mesh
from sparse_family import rel_l2

# samples, tokens, H P, G N, heads (the lanes of dt), taps, groups,
# tokens a grid step where not the kernels' own
SHAPES = {
    "three_tiles_of_8": (2, 24, 256, 128, 4, 4, 2, 8),
    "one_tile_groups_of_two_lane_tiles": (1, 32, 512, 256, 8, 4, 2, K.ROWS),
    "shorter_than_the_taps": (1, 8, 128, 128, 2, 9, 1, K.ROWS),
}
BF16_SHAPES = {
    "three_tiles_of_16": (2, 48, 256, 128, 4, 4, 2, 16),
    "one_tile": (1, 32, 128, 128, 2, 4, 1, K.ROWS),
}
EPS = 1e-5


def _operands(shape, dtype):
    """(zxbcdt, taps, bias, y, scale) and a weight an output."""
    b, t, inner, state, heads, k, _, _ = shape
    lanes = inner + 2 * state
    keys = jax.random.split(jax.random.PRNGKey(t + k), 8)
    weigh = [jax.random.normal(key, (b, t, w))
             for key, w in zip(keys[5:], (inner, state, state))]
    return (jax.random.normal(keys[0], (b, t, inner + lanes + heads)
                              ).astype(dtype),
            0.5 * jax.random.normal(keys[1], (k, lanes)),
            jax.random.normal(keys[2], (lanes,)),
            jax.random.normal(keys[3], (b, t, inner)).astype(dtype),
            1.0 + 0.1 * jax.random.normal(keys[4], (inner,))), weigh


def _taps(shape, kernel: bool):
    _, _, inner, state, _, _, _, _ = shape
    lanes = inner + 2 * state
    if kernel:
        return lambda zxbcdt, taps, bias: K.taps_silu(
            zxbcdt, taps, bias, inner, (inner, state, state), True)

    def xla(zxbcdt, taps, bias):
        xbc = sparse_lm.causal_taps_silu(zxbcdt[..., inner:inner + lanes],
                                         taps, bias)
        return (xbc[..., :inner], xbc[..., inner:inner + state],
                xbc[..., inner + state:])
    return xla


def _gate_norm(shape, kernel: bool):
    _, _, inner, _, _, _, groups, _ = shape
    if kernel:
        return lambda y, zxbcdt, scale: (K.gate_norm(
            y, zxbcdt, scale, groups, EPS, True),)
    return lambda y, zxbcdt, scale: (sparse_lm.gated_group_norm(
        y, zxbcdt[..., :inner], scale, groups, EPS),)


def _value_and_grads(fn, operands, weigh):
    """The outputs and every operand's gradient through a rematerialised
    call of ``fn``."""
    def loss(*o):
        outs = jax.checkpoint(fn)(*o)
        return sum(jnp.sum(out.astype(jnp.float32) * w)
                   for out, w in zip(outs, weigh)), outs
    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(*operands)
    return tuple(outs) + tuple(grads)


@functools.lru_cache(maxsize=None)
def _three(stage, name, dtype):
    """(the pass, its XLA lowering, that on the same numbers in f32), each
    the outputs and the three gradients."""
    shape = (SHAPES if dtype == "float32" else BF16_SHAPES)[name]
    (zxbcdt, taps, bias, y, scale), weigh = _operands(shape, dtype)
    make, operands = ((_taps, (zxbcdt, taps, bias)) if stage == "taps"
                      else (_gate_norm, (y, zxbcdt, scale)))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(K, "ROWS", shape[-1])
        got = _value_and_grads(make(shape, True), operands, weigh)
    want = _value_and_grads(make(shape, False), operands, weigh)
    exact = tuple(o.astype(jnp.float32) for o in operands)
    return got, want, _value_and_grads(make(shape, False), exact, weigh)


NAMES = {"taps": ("x", "B", "C", "d zxbcdt", "d taps", "d bias"),
         "gate_norm": ("out", "d y", "d zxbcdt", "d scale")}


@pytest.mark.parametrize("name", list(SHAPES))
@pytest.mark.parametrize("stage", list(NAMES))
def test_a_pass_is_its_xla_lowering_f32(stage, name):
    got, want, _ = _three(stage, name, "float32")
    for what, g, w in zip(NAMES[stage], got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        assert rel_l2(g, w) < 2e-6, what
    # the lanes of in_proj's output that are not the pass's get noughts
    _, _, inner, state, _, _, _, _ = SHAPES[name]
    d_zxbcdt = np.array(got[NAMES[stage].index("d zxbcdt")])
    mine = slice(inner, 2 * inner + 2 * state) if stage == "taps" \
        else slice(0, inner)
    assert np.abs(d_zxbcdt[..., mine]).min() > 0
    d_zxbcdt[..., mine] = 0
    assert not d_zxbcdt.any()


@pytest.mark.parametrize("name", list(BF16_SHAPES))
@pytest.mark.parametrize("stage", list(NAMES))
def test_in_bfloat16_a_pass_lies_as_near_the_f32_numbers_as_the_xla_code(
        stage, name):
    """bfloat16 operands: each result in the lowering's dtype and no
    further from the same expression in f32 than the XLA lowering's own
    distance (half as far again, or a bfloat16 step of 2^-8)."""
    got, want, true = _three(stage, name, "bfloat16")
    for what, g, w, t in zip(NAMES[stage], got, want, true):
        assert g.shape == w.shape and g.dtype == w.dtype, what
        assert rel_l2(g, t) < max(1.5 * rel_l2(w, t), 2 ** -8), (
            what, rel_l2(g, t), rel_l2(w, t))


def test_a_change_at_token_6_moves_tokens_6_to_9_across_the_tiles_edge(
        monkeypatch):
    """Tiles of 8 tokens: tokens 8 and 9 read tokens 6 and 7 as the rows
    before their tile, and the cotangent of token 6 is made of the tile
    after's ``dz``."""
    monkeypatch.setattr(K, "ROWS", 8)
    shape = SHAPES["three_tiles_of_8"]
    (zxbcdt, taps, bias, _, _), weigh = _operands(shape, "float32")
    inner, state, k = shape[2], shape[3], shape[5]
    xbc = slice(inner, 2 * inner + 2 * state)
    fn = jax.jit(_taps(shape, True))
    was, moved = fn(zxbcdt, taps, bias), fn(
        zxbcdt.at[:, 6, xbc].add(1.0), taps, bias)
    for a, b in zip(was, moved):
        np.testing.assert_array_equal(a[:, :6], b[:, :6])
        assert float(jnp.abs(a[:, 6:6 + k] - b[:, 6:6 + k]).min()) > 0
        np.testing.assert_array_equal(a[:, 6 + k:], b[:, 6 + k:])

    def d_zxbcdt(weigh):
        return jax.jit(jax.grad(lambda z: sum(
            jnp.sum(o * w) for o, w in zip(_taps(shape, True)(z, taps, bias),
                                           weigh))))(zxbcdt)
    at_9 = [w.at[:, 9].add(1.0) for w in weigh]
    a, b = d_zxbcdt(weigh), d_zxbcdt(at_9)
    np.testing.assert_array_equal(a[:, :6], b[:, :6])
    assert float(jnp.abs(a[:, 6:10, xbc] - b[:, 6:10, xbc]).min()) > 0
    np.testing.assert_array_equal(a[:, 10:], b[:, 10:])


TINY = dict(hidden_size=64, num_hidden_layers=1, layer_kinds=("mamba2",),
            num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=96,
            text_seq_len=24, image_grid=4, vocab_text=48, vocab_image=48,
            dtype="float32")
# a mixer both passes take (the scan is XLA code: a chunk of 8)
FITS = dict(mamba_num_heads=4, mamba_head_dim=64, ssm_groups=2,
            ssm_state_size=128, ssm_chunk=8)


@pytest.mark.parametrize("tokens, sizes, taps_why, norm_why", [
    (40, {}, None, None),
    (20, {}, "20 tokens are not whole tiles of 8 rows",
     "20 tokens are not whole tiles of 8 rows"),
    (3, {}, "3 tokens are not whole tiles of 8 rows",
     "3 tokens are not whole tiles of 8 rows"),
    (16, dict(mamba_head_dim=48), "a part of 192 lanes is not whole "
     "128-lane tiles", "a group of 96 lanes is not whole 128-lane tiles"),
    (16, dict(mamba_head_dim=32, ssm_state_size=192),
     "a part of 384 lanes at lane 256 is no column block of its width",
     "a group of 64 lanes is not whole 128-lane tiles"),
    (16, dict(conv_kernel=10),
     "10 taps reach past the 8 rows before a tile", None),
    # the tests' tiny model
    (40, dict(mamba_head_dim=8, ssm_state_size=16),
     "a part of 32 lanes is not whole 128-lane tiles",
     "a group of 16 lanes is not whole 128-lane tiles"),
])
def test_a_site_takes_its_pass_or_says_why_not(tokens, sizes, taps_why,
                                               norm_why, monkeypatch,
                                               lowering_record):
    """The predicates on the local shapes, through the sites: the record
    holds the reason, and a refused call is the XLA lowering's result bit
    for bit."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = NemotronHLMConfig(**dict(TINY, **dict(FITS, **sizes)))
    inner, state = cfg.mamba_inner, cfg.ssm_groups * cfg.ssm_state_size
    shape = (1, tokens, inner, state, cfg.mamba_num_heads, cfg.conv_kernel,
             cfg.ssm_groups, K.ROWS)
    (zxbcdt, taps, bias, y, scale), _ = _operands(shape, "float32")
    sites = {
        sparse_lm.TAPS_SITE: (
            sparse_lm.ssm_taps, _taps(shape, False), (zxbcdt, taps, bias),
            sparse_lm._taps_key(tokens, cfg), taps_why),
        sparse_lm.GATE_NORM_SITE: (
            sparse_lm.ssm_gate_norm,
            lambda *o: _gate_norm(shape, False)(*o)[0], (y, zxbcdt, scale),
            sparse_lm._gate_norm_key(tokens, cfg), norm_why)}
    for site, (call, xla, operands, key, why) in sites.items():
        got = jax.jit(functools.partial(call, mesh=None, cfg=cfg))(*operands)
        want = jax.jit(xla)(*operands)
        assert lowering_record.why_not(site, key) == why, site
        assert lowering_record.recorded(site, key) == {"why_not": why}
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if why is None:
                assert rel_l2(g, w) < 2e-6, site
            else:
                np.testing.assert_array_equal(g, w)
    assert sparse_lm._taps_key(tokens, cfg) == (
        tokens, inner, (inner, state, state), cfg.conv_kernel, 4)
    assert sparse_lm._gate_norm_key(tokens, cfg) == (
        tokens, inner, cfg.ssm_groups, 4)


def test_tiles_that_pass_vmem_are_refused_and_the_cells_fit(monkeypatch):
    cell = (8192, 4096, (4096, 1024, 1024), 4, 2)
    assert K.taps_fit(*cell) is None and K.rows_tile(8192, 2) == 256
    assert K.gate_norm_fit(8192, 4096, 8, 2) is None
    assert K.rows_tile(8200, 4) == 200 and K.rows_tile(48, 2) == 48
    monkeypatch.setattr(K, "_VMEM", 1 << 20)
    assert K.taps_fit(*cell).startswith("a tile of 256 x 6144 needs ")
    assert K.gate_norm_fit(8192, 4096, 8, 2).startswith(
        "a tile of 256 x 4096 needs ")


def _mixer_loss_and_grads(cfg, params, text, image):
    model = sparse_lm.build(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, image)[0]))(params)


def test_the_mixer_with_both_passes_is_the_mixer_with_both_refused(
        monkeypatch, lowering_record):
    """A mixer layer (and the expert layer a model has to have) under the
    layers' rematerialisation, f32: loss and every gradient leaf with the
    passes on against the same model whose two sites refuse (the XLA
    lowerings), and ``ssm_layout`` says which ran."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    monkeypatch.setattr(K, "ROWS", 8)
    cfg = NemotronHLMConfig(**dict(
        TINY, **FITS, num_hidden_layers=2, layer_kinds=("mamba2", "experts"),
        expert_width=32, shared_expert_width=48, num_experts=8,
        experts_per_token=2, experts_held=4, expert_offset=2, head_chunk=16))
    cfg.validate()
    params = sparse_lm.init_params(sparse_lm.build(cfg),
                                   jax.random.PRNGKey(3))
    ssm = params["params"]["layer_0"]["ssm"]
    for i, name in enumerate(("conv_bias", "norm", "taps")):
        ssm[name] = ssm[name] + 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), ssm[name].shape)
    rng = np.random.default_rng(0)
    text = jnp.asarray(rng.integers(2, 48, (2, 24)), jnp.int32)
    image = jnp.asarray(rng.integers(0, 48, (2, 16)), jnp.int32)
    loss, grads = _mixer_loss_and_grads(cfg, params, text, image)
    layout = sparse_lm.ssm_layout(cfg)
    assert ("taps, bias and SiLU: one pass a direction, x, B and C written "
            "apart; gate and group norm: one pass a direction; ") in layout
    refused = "refused by the test"
    monkeypatch.setattr(K, "taps_fit", lambda *a: refused)
    monkeypatch.setattr(K, "gate_norm_fit", lambda *a: refused)
    jax.clear_caches()
    want_loss, want = _mixer_loss_and_grads(cfg, params, text, image)
    layout = sparse_lm.ssm_layout(cfg)
    assert (f"taps, bias and SiLU: XLA code ({refused}); gate and group "
            f"norm: XLA code ({refused}); ") in layout
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want)):
        assert rel_l2(g, w) < 1e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_a_shards_samples_and_the_vectors_of_one_device(
        nested, monkeypatch, inside_manual_dp, lowering_record):
    """dp 2 x fsdp 2 x tp 2: a shard holds one of the four samples, every
    lane of it; the replicated taps, bias and scale get the one-device
    gradient with no sum written out."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    cfg = NemotronHLMConfig(**dict(TINY, **FITS))
    shape = (4, 16, cfg.mamba_inner, 256, 4, 4, 2, K.ROWS)
    (zxbcdt, taps, bias, y, scale), weigh = _operands(shape, "float32")

    def value_and_grads(mesh_):
        def f(taps, bias, scale, zxbcdt, y, *weigh):
            x, bm, cm = sparse_lm.ssm_taps(zxbcdt, taps, bias, mesh=mesh_,
                                           cfg=cfg, scope="conv")
            out = sparse_lm.ssm_gate_norm(y + x, zxbcdt, scale, mesh=mesh_,
                                          cfg=cfg, scope="gate_norm")
            return sum(jnp.sum(o * w) for o, w in zip((out, bm, cm), weigh)
                       ), out
        vg = jax.value_and_grad(f, argnums=tuple(range(5)), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (False,) * 3 + (True,) * 5,
                                  tuple(range(5)))
        return jax.jit(vg)

    operands = (taps, bias, scale, zxbcdt, y, *weigh)
    (_, out_m), g_m = value_and_grads(mesh)(*operands)
    (_, out_1), g_1 = value_and_grads(None)(*operands)
    assert len(out_m.sharding.device_set) == 8
    assert lowering_record.why_not(
        sparse_lm.TAPS_SITE, sparse_lm._taps_key(16, cfg)) is None
    assert lowering_record.why_not(
        sparse_lm.GATE_NORM_SITE, sparse_lm._gate_norm_key(16, cfg)) is None
    np.testing.assert_allclose(out_m, out_1, rtol=1e-6, atol=1e-6)
    for name, got, want in zip(("taps", "bias", "scale", "zxbcdt", "y"),
                               g_m, g_1):
        assert rel_l2(got, want) < 1e-6, name
