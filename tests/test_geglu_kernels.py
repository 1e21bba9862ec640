"""Fused Pallas GEGLU FF kernel (ops/pallas/geglu_kernels.py): numerics
against the unfused lowering, model-level fused-vs-unfused parity, and the
residual-shrink property the fusion exists for (PERF.md r3 headroom #1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.pallas.geglu_kernels import (geglu_ff, geglu_supported)


def _ref(x, wi, wg, wo, bi, bg, bo):
    return ((jnp.dot(x, wi) + bi)
            * jax.nn.gelu(jnp.dot(x, wg) + bg)) @ wo + bo


def _operands(key, m=256, d=128, k=512, dtype=jnp.float32):
    ks = jax.random.split(key, 7)
    return (jax.random.normal(ks[0], (m, d), dtype) * 0.5,
            jax.random.normal(ks[1], (d, k), dtype) * 0.05,
            jax.random.normal(ks[2], (d, k), dtype) * 0.05,
            jax.random.normal(ks[3], (k, d), dtype) * 0.05,
            jax.random.normal(ks[4], (k,), dtype) * 0.1,
            jax.random.normal(ks[5], (k,), dtype) * 0.1,
            jax.random.normal(ks[6], (d,), dtype) * 0.1)


class TestKernelNumerics:
    def test_forward_matches_unfused(self):
        ops = _operands(jax.random.PRNGKey(0))
        out = geglu_ff(*ops, 128, 256, True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(*ops)),
                                   rtol=1e-5, atol=1e-5)

    def test_backward_matches_xla_autodiff(self):
        ops = _operands(jax.random.PRNGKey(1))

        def loss(fn):
            return lambda *a: jnp.sum(jnp.sin(fn(*a)))

        g_k = jax.grad(loss(lambda *a: geglu_ff(*a, 128, 256, True)),
                       argnums=tuple(range(7)))(*ops)
        g_r = jax.grad(loss(_ref), argnums=tuple(range(7)))(*ops)
        for a, b in zip(g_k, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_uneven_tiles_and_jit(self):
        # m=384 with block_m=256 -> picked block divides (128); k=640
        ops = _operands(jax.random.PRNGKey(2), m=384, k=640)
        fn = jax.jit(lambda *a: geglu_ff(*a, 256, 512, True))
        np.testing.assert_allclose(np.asarray(fn(*ops)),
                                   np.asarray(_ref(*ops)),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_close_to_f32_reference(self):
        ops = _operands(jax.random.PRNGKey(3))
        xb = [a.astype(jnp.bfloat16) for a in ops]
        out = geglu_ff(*xb, 128, 256, True).astype(jnp.float32)
        ref = _ref(*ops)
        scale = float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(out - ref))) / scale < 2e-2

    def test_supported_gate(self):
        assert geglu_supported(5120, 1024, 4096, jnp.bfloat16)
        assert not geglu_supported(192, 64, 256, jnp.bfloat16)   # d%128
        assert not geglu_supported(64, 128, 512, jnp.bfloat16)   # m small
        assert not geglu_supported(256, 128, 512, jnp.int8)


class TestModelIntegration:
    """ff_fusion wiring: fused model == unfused model (same params), and
    the fused plain block's FF residuals shrink to the kernel inputs."""

    @staticmethod
    def _model(ff_fusion, skip):
        from dalle_tpu.config import flagship_model_config
        from dalle_init import init_params
        from dalle_tpu.models.dalle import DALLE

        cfg = flagship_model_config(
            depth=9, dim=128, heads=2, head_dim=64, text_seq_len=16,
            image_grid=4, vocab_text=64, vocab_image=32, head_chunk=0,
            remat_skip_blocks=skip, ff_fusion=ff_fusion)
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        return cfg, model, params

    def test_fused_matches_unfused_loss_and_grads(self, monkeypatch):
        from dalle_tpu.models import attention
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)

        cfg, model, params = self._model("none", 1)
        _, model_f, params_f = self._model("plain", 1)
        # identical param trees (DenseKernel mirrors nn.Dense)
        assert (jax.tree.structure(params)
                == jax.tree.structure(params_f))
        text = jnp.zeros((2, cfg.text_seq_len), jnp.int32)
        image = jnp.ones((2, cfg.image_seq_len), jnp.int32)

        def loss(m):
            return lambda p: m.apply(p, text, image)[0]

        # one jitted program a model: loss and gradients in one trace
        # (four eager passes through the interpreted kernels took 150-190 s)
        l_u, g_u = jax.jit(jax.value_and_grad(loss(model)))(params)
        l_f, g_f = jax.jit(jax.value_and_grad(loss(model_f)))(params)
        l_u, l_f = float(l_u), float(l_f)
        assert abs(l_u - l_f) / abs(l_u) < 1e-3, (l_u, l_f)

        flat_u, _ = jax.tree_util.tree_flatten(g_u)
        flat_f, _ = jax.tree_util.tree_flatten(g_f)
        for a, b in zip(flat_u, flat_f):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-2, atol=2e-3)

    def test_param_tree_matches_dense_layout(self):
        # checkpoints trained before the DenseKernel refactor must load:
        # the FF param paths are {wi,gate,wo}/kernel with Dense's shapes
        cfg, _, params = self._model("none", 0)
        tr = params["params"]["transformer"]
        ff = (tr.get("cycle") or tr)["block_0"]["ff"]
        assert set(ff) == {"wi", "gate", "wo"}
        inner = cfg.ff_mult * cfg.dim
        assert ff["wi"]["kernel"].shape == (cfg.dim, inner)
        assert ff["gate"]["kernel"].shape == (cfg.dim, inner)
        assert ff["wo"]["kernel"].shape == (inner, cfg.dim)
        # nn.Dense parity includes the default biases (dalle-pytorch
        # FeedForward uses biased nn.Linear); dropping them broke
        # checkpoint compatibility in r4 until review caught it
        assert ff["wi"]["bias"].shape == (inner,)
        assert ff["gate"]["bias"].shape == (inner,)
        assert ff["wo"]["bias"].shape == (cfg.dim,)


@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_kernel_matches_single_device(nested, monkeypatch,
                                                inside_manual_dp):
    """On a dp=2 x fsdp=2 x tp=2 mesh the fused FF runs per shard — token
    rows over the batch axes, the inner dimension over tp with one psum
    of the partial products and the output bias added once: values and
    gradients must equal the unwrapped one-device kernel. ``nested``: the
    call sits where the gradient accumulation puts it, inside a
    ``shard_map`` manual over ``dp``, and binds the other axes only."""
    from dalle_tpu.config import flagship_model_config
    from dalle_tpu.models import attention
    from dalle_tpu.models.transformer import GEGLUFeedForward
    from dalle_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = flagship_model_config(dim=128, heads=2, head_dim=64,
                                dtype="float32")
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128, 128)) * 0.5
    w = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    params = GEGLUFeedForward(cfg, fuse=True).init(jax.random.PRNGKey(2), x)
    # zero-init biases would hide a bias added tp times (or never)
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                              p.shape), params)

    def loss(mesh_, nested=False):
        ff = GEGLUFeedForward(cfg, fuse=True, mesh=mesh_)

        def f(p, x, w):
            out = ff.apply(p, x)
            return jnp.sum(out * w), out
        vg = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
        if nested:
            vg = inside_manual_dp(vg, mesh_, (False, True, True), (0, 1))
        return jax.jit(vg)

    (_, out_m), g_m = loss(mesh, nested)(params, x, w)
    (_, out_1), g_1 = loss(None)(params, x, w)
    assert len(out_m.sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_1),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_m), jax.tree.leaves(g_1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
