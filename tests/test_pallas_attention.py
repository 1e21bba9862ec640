"""Pallas fused axial attention vs the XLA reference path.

Runs the kernels in interpret mode on the CPU mesh: forward must match the
dense-mask oracle, and the custom flash-style backward must match XLA
autodiff through the reference implementation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import ATTN_AXIAL_COL, ATTN_AXIAL_ROW
from dalle_tpu.models.attention import (_as_lanes, _fused_lanes,
                                        axial_attention, dense_zoo_attention)

# 4 heads of 32: one 128-lane tile, the narrowest width the kernels take
TEXT, GRID, H, D = 16, 4, 4, 32


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    """The kernels run on the CPU, interpreted."""
    from dalle_tpu.models import attention
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)


def fused_on_lanes(q, k, v, attn_type, text_len, grid, conv_kernel=11):
    """A zoo layer's kernel on the lanes view of (B, T, H, d) operands."""
    return _as_lanes(lambda *qkv: _fused_lanes(
        *qkv, q.shape[-1], attn_type, text_len, grid, conv_kernel), q, k, v)


def _qkv(key, b=2, t=TEXT + GRID * GRID):
    ks = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, t, H, D), jnp.float32)  # noqa
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("attn_type", [ATTN_AXIAL_ROW, ATTN_AXIAL_COL])
class TestFusedAxial:
    def test_forward_matches_dense_oracle(self, attn_type):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        got = fused_on_lanes(q, k, v, attn_type, TEXT, GRID)
        want = dense_zoo_attention(q, k, v, attn_type, TEXT, GRID)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_backward_matches_xla_autodiff(self, attn_type):
        q, k, v = _qkv(jax.random.PRNGKey(1))
        w = jax.random.normal(jax.random.PRNGKey(2), q.shape)

        def loss_fused(q, k, v):
            out = fused_on_lanes(q, k, v, attn_type, TEXT, GRID)
            return jnp.sum(out * w)

        def loss_ref(q, k, v):
            out = axial_attention(q, k, v, attn_type, TEXT, GRID)
            return jnp.sum(out * w)

        g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_jit_and_odd_line_packing(self, attn_type):
        """Grid whose line count doesn't divide 128/n cleanly still packs
        (whole lines per block, block count divides line count)."""
        grid = 6
        t = TEXT + grid * grid
        q, k, v = _qkv(jax.random.PRNGKey(3), t=t)
        got = jax.jit(lambda q, k, v: fused_on_lanes(
            q, k, v, attn_type, TEXT, grid))(q, k, v)
        want = dense_zoo_attention(q, k, v, attn_type, TEXT, grid)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("attn_type", ["conv_like", "full"])
class TestFusedWindow:
    """conv_like / full layers through the Pallas window kernel."""

    def test_forward_matches_dense_oracle(self, attn_type):
        q, k, v = _qkv(jax.random.PRNGKey(4))
        got = fused_on_lanes(q, k, v, attn_type, TEXT, GRID, conv_kernel=3)
        want = dense_zoo_attention(q, k, v, attn_type, TEXT, GRID,
                                   conv_kernel=3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_backward_matches_xla_autodiff(self, attn_type):
        q, k, v = _qkv(jax.random.PRNGKey(5))
        w = jax.random.normal(jax.random.PRNGKey(6), q.shape)

        def loss_fused(q, k, v):
            out = fused_on_lanes(q, k, v, attn_type, TEXT, GRID,
                                 conv_kernel=3)
            return jnp.sum(out * w)

        def loss_ref(q, k, v):
            out = dense_zoo_attention(q, k, v, attn_type, TEXT, GRID,
                                      conv_kernel=3)
            return jnp.sum(out * w)

        g_fused = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)

    def test_multi_group_grid(self, attn_type):
        """A grid large enough that queries span several key groups and
        conv windows overlap group boundaries (dk/dv scratch accumulation)."""
        grid = 8
        t = TEXT + grid * grid
        q, k, v = _qkv(jax.random.PRNGKey(7), t=t)
        w = jax.random.normal(jax.random.PRNGKey(8), q.shape)

        def loss(fn):
            def inner(q, k, v):
                return jnp.sum(fn(q, k, v) * w)
            return inner

        fused = lambda q, k, v: fused_on_lanes(  # noqa: E731
            q, k, v, attn_type, TEXT, grid, conv_kernel=5)
        dense = lambda q, k, v: dense_zoo_attention(  # noqa: E731
            q, k, v, attn_type, TEXT, grid, conv_kernel=5)
        np.testing.assert_allclose(np.asarray(fused(q, k, v)),
                                   np.asarray(dense(q, k, v)),
                                   rtol=2e-4, atol=2e-5)
        g_fused = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


class TestRematPolicyPinsKernelReplay:
    """The save_ctx/save_attn remat policies hinge on checkpoint_name
    applied to residual tracers INSIDE the kernels' custom_vjp fwd rules
    (attention_kernels._vjp_fwd): without that, rematerialisation replays
    the forward Pallas kernel in backward just to regenerate stats/out.
    Pin the behavior by counting pallas_call equations in the grad jaxpr:
    blanket remat = fwd (primal) + fwd (replay) + bwd per call site;
    save_ctx prunes the replay."""

    @staticmethod
    def _pallas_count(policy, monkeypatch):
        from dalle_tpu.config import flagship_model_config
        from dalle_tpu.models import attention
        from dalle_init import init_params
        from dalle_tpu.models.dalle import DALLE

        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)

        # 9 layers = one 2-repetition scan cycle of the 4 shared blocks
        # + the w_conv layer; tiny dims keep tracing fast while keeping
        # the flagship's structure (scan + remat + custom_vjp kernels)
        cfg = flagship_model_config(
            depth=9, dim=128, heads=4, head_dim=32, text_seq_len=16,
            image_grid=4, vocab_text=64, vocab_image=32,
            remat_skip_blocks=0, head_chunk=0, remat_policy=policy)
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
        image = jnp.zeros((1, cfg.image_seq_len), jnp.int32)

        def loss(p):
            return model.apply(p, text, image)[0]

        return str(jax.make_jaxpr(jax.grad(loss))(params)).count(
            "pallas_call")

    def test_save_ctx_prunes_forward_kernel_replay(self, monkeypatch):
        base = self._pallas_count(None, monkeypatch)
        pruned = self._pallas_count("save_ctx", monkeypatch)
        # blanket: 3 per call site (fwd, replayed fwd, bwd);
        # save_ctx: 2 per call site (fwd, bwd) -> ratio exactly 2/3
        assert pruned < base, (base, pruned)
        assert pruned * 3 == base * 2, (base, pruned)


# one line kernel (with the in-XLA column reorder) and one window kernel:
# the wrapper is the same for every zoo type
@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
@pytest.mark.parametrize("attn_type", [ATTN_AXIAL_COL, "conv_like"])
def test_per_shard_kernels_match_single_device(attn_type, nested,
                                               monkeypatch,
                                               inside_manual_dp,
                                               lowering_record):
    """GSPMD cannot partition a Mosaic kernel, so on a mesh the dispatcher
    runs the fused kernels per shard (batch over dp x fsdp, whole heads'
    lanes over tp): values and gradients must equal the unwrapped
    one-device call.
    ``nested``: called inside a ``shard_map`` manual over ``dp`` (the
    gradient accumulation's), the wrapper binds the other axes only."""
    from dalle_tpu.models import attention
    from dalle_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    # 8 heads of 32: tp=2 leaves each shard one 128-lane tile of 4 heads
    shape = (4, TEXT + GRID * GRID, 8, 32)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)

    def loss(mesh_, nested=False):
        def f(q, k, v, w):
            out = attention.zoo_attention(
                q, k, v, attn_type=attn_type, text_len=TEXT, grid=GRID,
                conv_kernel=3, mesh=mesh_)
            return jnp.sum(out * w), out
        vg = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)
        if nested:
            vg = inside_manual_dp(vg, mesh_, (True,) * 4, (0, 1, 2))
        return jax.jit(vg)

    (_, out_m), g_m = loss(mesh, nested)(q, k, v, w)
    # the kernel, not the XLA lowering, on the shards' local shapes
    assert lowering_record.why_not(
        f"{attn_type} attention", (32, 128, TEXT + GRID * GRID, TEXT)) is None
    (_, out_1), g_1 = loss(None)(q, k, v, w)
    assert len(out_m.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_1),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(g_m, g_1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


ZOO = [ATTN_AXIAL_ROW, ATTN_AXIAL_COL, "conv_like", "full"]


def _fused(q, k, v, attn_type, grid=GRID):
    return fused_on_lanes(q, k, v, attn_type, TEXT, grid, conv_kernel=3)


@pytest.mark.parametrize("head_dim", [32, 64, 128])
@pytest.mark.parametrize("attn_type", ZOO)
class TestLanePacking:
    """128 // head_dim heads share a 128-lane tile (4 / 2 / 1), separated
    by lane masks; 256 lanes = two tiles a sample, so the grid's second
    axis and the statistics' per-tile heads are walked too."""

    @staticmethod
    def _qkv(head_dim, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        shape = (2, TEXT + GRID * GRID, 256 // head_dim, head_dim)
        return [jax.random.normal(kk, shape, jnp.float32) for kk in ks]

    def test_forward_matches_dense_oracle(self, attn_type, head_dim):
        q, k, v, _ = self._qkv(head_dim, 11)
        want = dense_zoo_attention(q, k, v, attn_type, TEXT, GRID,
                                   conv_kernel=3)
        np.testing.assert_allclose(np.asarray(_fused(q, k, v, attn_type)),
                                   np.asarray(want), rtol=2e-4, atol=2e-5)

    def test_backward_matches_xla_autodiff(self, attn_type, head_dim):
        q, k, v, w = self._qkv(head_dim, 12)
        g_fused = jax.jit(jax.grad(
            lambda *qkv: jnp.sum(_fused(*qkv, attn_type) * w),
            argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.jit(jax.grad(
            lambda *qkv: jnp.sum(dense_zoo_attention(
                *qkv, attn_type, TEXT, GRID, conv_kernel=3) * w),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("attn_type", ZOO)
def test_text_rows_dk_dv_are_the_two_parts_summed(attn_type):
    """The text rows' keys and values serve their own causal line and
    every image row's prefix. Before, two kernels gave the two parts and
    XLA added them; now one kernel sums them in VMEM: the gradient of the
    whole must be the sum of the gradients through the text rows' and
    through the image rows' outputs, each of which the dense oracle
    confirms."""
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    shape = (2, TEXT + GRID * GRID, 4, 32)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
    is_text = (jnp.arange(shape[1]) < TEXT)[None, :, None, None]

    def grads(fn, weight):
        return jax.jit(jax.grad(lambda k, v: jnp.sum(fn(q, k, v) * weight),
                                argnums=(0, 1)))(k, v)

    fused = lambda q, k, v: _fused(q, k, v, attn_type)  # noqa: E731
    dense = lambda q, k, v: dense_zoo_attention(  # noqa: E731
        q, k, v, attn_type, TEXT, GRID, conv_kernel=3)
    whole = grads(fused, w)
    own = grads(fused, jnp.where(is_text, w, 0.0))
    prefix = grads(fused, jnp.where(is_text, 0.0, w))
    for got, a, b, a_ref, b_ref in zip(
            whole, own, prefix, grads(dense, jnp.where(is_text, w, 0.0)),
            grads(dense, jnp.where(is_text, 0.0, w))):
        assert float(jnp.abs(a[:, :TEXT]).max()) > 0
        assert float(jnp.abs(b[:, :TEXT]).max()) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(a_ref),
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(np.asarray(b), np.asarray(b_ref),
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(np.asarray(got[:, :TEXT]),
                                   np.asarray((a + b)[:, :TEXT]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("attn_type", [ATTN_AXIAL_ROW, "conv_like"])
def test_odd_head_count_takes_the_xla_lowering_and_says_so(
        attn_type, monkeypatch, caplog, lowering_record):
    """Three heads of 64 do not fill 128-lane tiles: the dispatcher takes
    the XLA lowering of the same attention and logs the choice once."""
    import logging

    from dalle_tpu.models import attention

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q, k, v = (jax.random.normal(kk, (2, TEXT + GRID * GRID, 3, 64),
                                 jnp.float32) for kk in ks)

    def run(q, k, v):
        return attention.zoo_attention(q, k, v, attn_type=attn_type,
                                       text_len=TEXT, grid=GRID,
                                       conv_kernel=3)
    # (it says a thing once: the fixture made it forget what it had said)
    with caplog.at_level(logging.INFO, logger=lowering_record.logger.name):
        jaxpr = jax.make_jaxpr(run)(q, k, v)
        got = run(q, k, v)
    assert "pallas_call" not in str(jaxpr)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith(f"{attn_type} attention")]
    assert said == [f"{attn_type} attention: XLA lowering (3 heads of 64 "
                    f"do not fill 128-lane tiles)"]
    assert lowering_record.why_not(
        f"{attn_type} attention", (64, 192, TEXT + GRID * GRID, TEXT)) == (
            "3 heads of 64 do not fill 128-lane tiles")
    want = dense_zoo_attention(q, k, v, attn_type, TEXT, GRID, conv_kernel=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("heads,on", [(4, 9), (3, 0)],
                         ids=["fills_tiles", "falls_back"])
def test_attn_layout_record_counts_the_layers_that_took_the_kernel(
        heads, on, monkeypatch, lowering_record):
    """The ``attn_layout`` attribute of the ``setup/warmup`` row: looked
    up in what the dispatcher did while the step was traced, at this
    model's own shapes — another model traced in the same process
    neither vouches for this one nor taints it."""
    from dalle_tpu.config import flagship_model_config
    from dalle_tpu.models import attention
    from dalle_tpu.models.dalle import DALLE, init_params

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)

    def trace(cfg):
        model = DALLE(cfg)
        params = jax.eval_shape(
            lambda: init_params(model, jax.random.PRNGKey(0)))
        jax.eval_shape(lambda p: model.apply(
            p, jnp.zeros((1, cfg.text_seq_len), jnp.int32),
            jnp.zeros((1, cfg.image_seq_len), jnp.int32))[0], params)

    small = dict(depth=9, head_dim=32, text_seq_len=16, image_grid=4,
                 vocab_text=64, vocab_image=32, head_chunk=0)
    cfg = flagship_model_config(dim=heads * 32, heads=heads, **small)
    # the other outcome, at another width, traced first
    trace(flagship_model_config(dim=(7 - heads) * 32, heads=7 - heads,
                                **small))
    assert attention.attn_layout_record(cfg) == \
        "lane-dense 128: 0 of 9 layers"       # this model: not traced yet
    trace(cfg)
    assert attention.attn_layout_record(cfg) == \
        f"lane-dense 128: {on} of 9 layers"
    # a tp that splits the heads' lanes reads its own local width
    from dalle_tpu.parallel.mesh import make_mesh
    assert attention.attn_layout_record(cfg, make_mesh(dp=4, tp=2)) == \
        "lane-dense 128: 0 of 9 layers"


def test_rotary_on_lanes_equals_rotary_per_head():
    """``apply_rotary_lanes`` on (B, T, H*d) is rotate-half rotary on each
    head of (B, T, H, d) — ``x * cos + concat(-x2, x1) * sin`` in f32 —
    bit for bit; decode's single rows take the same function."""
    from dalle_tpu.models.attention import (apply_rotary_lanes,
                                            rotary_cos_sin)
    b, t, h, d = 2, 24, 6, 32
    x = jax.random.normal(jax.random.PRNGKey(41), (b, t, h, d),
                          jnp.float32).astype(jnp.bfloat16)
    pos = jnp.arange(t)
    cos, sin = (a[:, None, :] for a in rotary_cos_sin(pos, d))
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    want = (xf * cos + rot * sin).astype(x.dtype)
    cos_l, sin_l = rotary_cos_sin(pos, d, heads=h)
    got = apply_rotary_lanes(x.reshape(b, t, h * d), cos_l, sin_l, d)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(b, t, h, d), np.float32),
        np.asarray(want, np.float32))
    # one row a sample, each at its own position (per-slot decode)
    row = apply_rotary_lanes(x[:, 5].reshape(b, h * d), cos_l[jnp.array(
        [5, 5])], sin_l[jnp.array([5, 5])], d)
    np.testing.assert_array_equal(np.asarray(row, np.float32),
                                  np.asarray(got[:, 5], np.float32))


@pytest.mark.parametrize("width,head_dim,t,text,fits", [
    (1024, 64, 1280, 256, True),         # the flagship: 8 tiles of 2 heads
    (1792, 64, 1280, 256, True),         # XL: 28 heads = 14 tiles
    (512, 128, 1280, 256, True),         # one head a tile
    (128, 32, 32, 16, True),             # four heads a tile
    (1024, 32, 1280, 256, False),        # ... whose prefix scores all live
    (192, 64, 1280, 256, False),         # 3 heads of 64: a half-empty tile
    (192, 96, 1280, 256, False),         # 128 % head_dim
    (1024, 64, 256 + 64 * 64, 256, False),   # long context: past VMEM
], ids=["flagship", "xl", "d128", "d32", "d32_flagship_length", "odd_heads",
        "d96", "longctx"])
def test_one_predicate_says_which_shapes_take_the_kernel(
        width, head_dim, t, text, fits):
    from dalle_tpu.ops.pallas.attention_kernels import lane_dense_fits
    why_not = lane_dense_fits(width, head_dim, t, text)
    assert (why_not is None) == fits, why_not
