"""Block-wise quantization + 8-bit LAMB tests (reference-parity semantics:
lamb_8bit.py fp32-vs-8bit trajectories, small-tensor fp32 fallback)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.quant import (
    Quantized,
    dequantize_blockwise,
    dynamic_codebook,
    quantize_blockwise,
)
from dalle_tpu.optim.lamb import lamb
from dalle_tpu.optim.lamb8bit import Lamb8bitState, lamb8bit, optimizer_state_bytes


class TestCodebook:
    def test_shapes_and_monotonic(self):
        for signed in (True, False):
            cb = dynamic_codebook(signed)
            assert cb.shape == (256,)
            assert (np.diff(cb) > 0).all(), "codebook must be sorted unique"
            assert cb[-1] == pytest.approx(1.0)
            assert 0.0 in cb
            if signed:
                assert cb[0] == pytest.approx(-1.0)
            else:
                assert (cb >= 0).all()

    def test_fine_resolution_near_zero(self):
        cb = dynamic_codebook(True)
        near = np.abs(cb[np.abs(cb) < 1e-3])
        assert near.size > 10, "dynamic map should have entries near zero"


class TestRoundTrip:
    @pytest.mark.parametrize("signed", [True, False])
    def test_error_bound_normal_data(self, signed):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(10_000,)).astype(np.float32)
        if not signed:
            x = np.abs(x)
        q = quantize_blockwise(jnp.asarray(x), block_size=4096, signed=signed)
        y = np.asarray(dequantize_blockwise(q))
        # dynamic 8-bit: relative block error well under 2%
        rel = np.abs(y - x).mean() / np.abs(x).mean()
        assert rel < 0.02, rel

    def test_exact_for_codebook_values(self):
        cb = dynamic_codebook(True)
        x = jnp.asarray(cb) * 3.7  # single block, absmax 3.7
        q = quantize_blockwise(x, block_size=256)
        y = dequantize_blockwise(q)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6)

    def test_zero_block(self):
        x = jnp.zeros((5000,))
        q = quantize_blockwise(x)
        y = dequantize_blockwise(q)
        np.testing.assert_array_equal(np.asarray(y), 0.0)

    def test_shape_restored_and_padding_dropped(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (33, 77))
        q = quantize_blockwise(x, block_size=1024)
        assert q.codes.shape == (3, 1024)  # 2541 elems -> 3 blocks
        y = dequantize_blockwise(q)
        assert y.shape == (33, 77)

    def test_under_jit(self):
        @jax.jit
        def roundtrip(x):
            return dequantize_blockwise(quantize_blockwise(x))
        x = jax.random.normal(jax.random.PRNGKey(1), (4096,))
        y = roundtrip(x)
        assert jnp.abs(y - x).mean() < 0.02


class TestLamb8bit:
    def _problem(self, big=False):
        n = 70_000 if big else 64
        rng = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(rng, (n,)) * 0.1,
                  "b": jnp.zeros((8,))}
        return params

    def test_small_tensors_match_fp32_exactly(self):
        """All tensors below min_8bit_size -> trajectories identical."""
        params = self._problem(big=False)
        kw = dict(learning_rate=0.01, weight_decay=0.01, max_grad_norm=1.0)
        tx32, tx8 = lamb(**kw), lamb8bit(**kw, min_8bit_size=1 << 20)
        s32, s8 = tx32.init(params), tx8.init(params)
        p32, p8 = params, params
        for i in range(5):
            g = jax.tree.map(
                lambda p: jnp.sin(p * (i + 1)) * 0.1, p32)
            u32, s32 = tx32.update(g, s32, p32)
            u8, s8 = tx8.update(g, s8, p8)
            p32 = jax.tree.map(jnp.add, p32, u32)
            p8 = jax.tree.map(jnp.add, p8, u8)
        for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p8)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7)

    def test_8bit_tracks_fp32_closely(self):
        params = self._problem(big=True)
        kw = dict(learning_rate=0.01, weight_decay=0.0, max_grad_norm=None)
        tx32, tx8 = lamb(**kw), lamb8bit(**kw, min_8bit_size=4096)
        s32, s8 = tx32.init(params), tx8.init(params)
        p32, p8 = params, params
        for i in range(10):
            g = jax.tree.map(lambda p: jnp.cos(p + i * 0.1) * 0.1, p32)
            u32, s32 = tx32.update(g, s32, p32)
            g8 = jax.tree.map(lambda p: jnp.cos(p + i * 0.1) * 0.1, p8)
            u8, s8 = tx8.update(g8, s8, p8)
            p32 = jax.tree.map(jnp.add, p32, u32)
            p8 = jax.tree.map(jnp.add, p8, u8)
        w32 = np.asarray(p32["w"])
        w8 = np.asarray(p8["w"])
        drift = np.abs(w32 - w8).mean() / (np.abs(w32).mean() + 1e-9)
        assert drift < 0.02, drift

    def test_large_moments_are_uint8(self):
        params = self._problem(big=True)
        tx = lamb8bit(learning_rate=0.01, min_8bit_size=4096)
        state = tx.init(params)
        mu_w = state.mu["w"]
        assert isinstance(mu_w, Quantized)
        assert mu_w.codes.dtype == jnp.uint8
        assert not isinstance(state.mu["b"], Quantized)
        # memory: quantized state for w is ~1 byte/elem + absmax overhead
        nbytes = optimizer_state_bytes(state)
        dense = 2 * (70_000 + 8) * 4
        assert nbytes < dense * 0.4, (nbytes, dense)

    def test_state_update_under_jit(self):
        params = self._problem(big=True)
        tx = lamb8bit(learning_rate=0.01, min_8bit_size=4096)
        state = tx.init(params)

        @jax.jit
        def step(p, s):
            g = jax.tree.map(lambda x: x * 0.01 + 0.001, p)
            u, s = tx.update(g, s, p)
            return jax.tree.map(jnp.add, p, u), s

        p, s = step(params, state)
        assert np.isfinite(np.asarray(p["w"])).all()
        # second moment must be nonnegative after dequant
        from dalle_tpu.ops.quant import dequantize_blockwise as dq
        assert (np.asarray(dq(s.nu["w"])) >= 0).all()


class TestPallasKernel:
    def test_matches_pure_jax_exactly(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (10_000,))
        for signed in (True, False):
            data = x if signed else jnp.abs(x)
            ref = quantize_blockwise(data, 4096, signed=signed,
                                     use_pallas=False)
            got = quantize_blockwise(data, 4096, signed=signed,
                                     use_pallas=True, interpret=True)
            np.testing.assert_array_equal(np.asarray(got.codes),
                                          np.asarray(ref.codes))
            np.testing.assert_allclose(np.asarray(got.absmax),
                                       np.asarray(ref.absmax))

    def test_rejects_bad_block(self):
        from dalle_tpu.ops.pallas.quant_kernels import quantize_blocks_pallas
        with pytest.raises(ValueError):
            quantize_blocks_pallas(jnp.zeros((1, 100)))

    @pytest.mark.parametrize("n_blocks", [6, 5])
    def test_per_shard_kernel_matches_single_device(self, n_blocks):
        """On a dp=2 x fsdp=2 x tp=2 mesh the quantize kernel runs per
        shard of the block rows — over fsdp when the rows divide (6),
        replicated when they do not (5) — and lands exactly where
        opt_state_shardings places the codes. Byte-equal to one device."""
        from dalle_tpu.ops.quant import blocks_spec
        from dalle_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(dp=2, fsdp=2, tp=2)
        x = jax.random.normal(jax.random.PRNGKey(1), (n_blocks * 512 - 7,))
        quant = jax.jit(lambda x, mesh_: quantize_blockwise(
            x, 512, use_pallas=True, interpret=True, mesh=mesh_),
            static_argnums=1)
        got, ref = quant(x, mesh), quant(x, None)
        assert got.codes.sharding.spec == blocks_spec(mesh, n_blocks)
        np.testing.assert_array_equal(np.asarray(got.codes),
                                      np.asarray(ref.codes))
        np.testing.assert_array_equal(np.asarray(got.absmax),
                                      np.asarray(ref.absmax))
