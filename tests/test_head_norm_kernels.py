"""The one-pass head norm (ops/pallas/head_norm_kernels.py), interpreted on
the CPU, against the expression it replaces (a reshape to heads and
``rms_norm``) and its ``jax.grad``: 32- and 4-head widths, bf16 and f32, a
row count that takes more than one tile, two samples, heads of two lane
tiles; per shard on the 8-device mesh; and the rule that chooses it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import head_norm_kernels as K
from dalle_tpu.parallel.mesh import LANES_SPEC, make_mesh, per_shard

EPS = 1e-5

# heads, head_dim, (samples, rows), dtype
CASES = {
    "32_heads_bf16": (32, 128, (1, 64), "bfloat16"),
    "32_heads_f32": (32, 128, (1, 64), "float32"),
    "4_heads_bf16": (4, 128, (2, 104), "bfloat16"),
    "4_heads_f32": (4, 128, (2, 104), "float32"),
    "32_heads_two_tiles_bf16": (32, 128, (1, 512), "bfloat16"),
    "2_heads_of_256_f32": (2, 256, (1, 64), "float32"),
}


def by_reshape(x, scale, head_dim):
    return sparse_lm.rms_norm(x.reshape(*x.shape[:2], -1, head_dim), scale,
                              EPS).reshape(x.shape)


def rel_l2(a, b):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def _both(case):
    """(y, dx, dscale) of the kernel and of the expression it replaces."""
    heads, head_dim, rows, dtype = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    shape = (*rows, heads * head_dim)
    x = (jax.random.normal(keys[0], shape) * 2.0 + 0.3).astype(dtype)
    w = jax.random.normal(keys[1], shape).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(keys[2], (head_dim,))

    def outputs(norm):
        y, vjp = jax.vjp(norm, x, scale)
        return (y,) + vjp(w)

    return (outputs(lambda x, s: K.head_rms_norm(x, s, EPS, head_dim, True)),
            outputs(lambda x, s: by_reshape(x, s, head_dim)))


def _tolerance(case, f32, bf16):
    return f32 if CASES[case][3] == "float32" else bf16


@pytest.mark.parametrize("case", CASES)
def test_forward_is_the_reshaped_rms_norm(case):
    (y, _, _), (ref, _, _) = _both(case)
    assert y.dtype == ref.dtype and y.shape == ref.shape
    # bf16: the same f32 arithmetic but the order of a sum, so a result
    # may round to the other neighbour, here and there
    assert rel_l2(y, ref) < _tolerance(case, 1e-6, 1e-4)


@pytest.mark.parametrize("case", CASES)
def test_dx_is_the_gradient_of_the_reshaped_rms_norm(case):
    (_, dx, _), (_, ref, _) = _both(case)
    assert dx.dtype == ref.dtype
    assert rel_l2(dx, ref) < _tolerance(case, 1e-6, 1e-4)


@pytest.mark.parametrize("case", CASES)
def test_dscale_is_summed_over_rows_tiles_and_heads(case):
    (_, _, ds), (_, _, ref) = _both(case)
    assert ds.shape == ref.shape == (CASES[case][1],)
    assert ds.dtype == jnp.float32
    assert rel_l2(ds, ref) < 5e-6


def test_a_row_count_past_one_tile_takes_several():
    assert K.rows_tile(512, 4096) == 256
    assert K.rows_tile(8192, 512) == 2048
    assert K.rows_tile(104, 512) == 104
    assert K.rows_tile(8 * 137, 4096) == 8          # 8 x a prime


@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_whole_heads_and_dscale_of_one_device(nested, monkeypatch,
                                                        inside_manual_dp):
    """dp 2 x fsdp 2 x tp 2: a shard holds a sample's rows of two of the
    four heads; the replicated scale's gradient is the one-device value
    with no sum written out (the ``shard_map``'s transpose sums it over
    the axes it binds, the gradient accumulation's psum over ``dp``)."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (4, 24, 512)) * 2.0
    w = jax.random.normal(keys[1], x.shape)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (128,))
    norm = functools.partial(sparse_lm._norm_heads_shard, eps=EPS,
                             head_dim=128, lanes=512)

    def value_and_grads(mesh_):
        def f(scale, x, w):
            out = per_shard(norm, mesh_, (LANES_SPEC, P()), LANES_SPEC,
                            scope="qk_norm")(x, scale)
            return jnp.sum(out * w), out
        vg = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (False, True, True), (0, 1))
        return jax.jit(vg)

    (_, out_m), g_m = value_and_grads(mesh)(scale, x, w)
    (_, out_1), g_1 = value_and_grads(None)(scale, x, w)
    assert len(out_m.sharding.device_set) == 8
    assert sparse_lm._HEAD_NORMS[24, 512, 128] is None
    np.testing.assert_allclose(out_m, out_1, rtol=1e-6, atol=1e-6)
    for a, b in zip(g_m, g_1):
        assert rel_l2(a, b) < 1e-6


@pytest.mark.parametrize("tokens, width, head_dim, interpret, why", [
    (64, 512, 128, True, None),
    (8192, 4096, 128, True, None),
    (64, 512, 64, True, "head_dim 64 is not whole 128-lane tiles"),
    (60, 512, 128, True, "60 rows are not whole sublane tiles of 8"),
    (64, 640, 256, True, "640 lanes are not whole heads of 256"),
    (64, 1 << 18, 128, True, "8 rows of 262144 lanes pass a tile of "
                             "1048576 numbers"),
    (64, 512, 128, False, "no Mosaic backend"),
])
def test_head_norm_why_not(tokens, width, head_dim, interpret, why,
                           monkeypatch):
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    assert sparse_lm.head_norm_why_not(tokens, width, head_dim) == why


@pytest.mark.parametrize("shape, head_dim", [((2, 32, 256), 64),
                                             ((1, 12, 256), 128)])
def test_what_does_not_fit_is_todays_expression_bit_for_bit(shape, head_dim,
                                                            monkeypatch):
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], shape).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], shape).astype(jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (head_dim,))

    today = functools.partial(by_reshape, head_dim=head_dim)
    now = functools.partial(sparse_lm._norm_heads_shard, eps=EPS,
                            head_dim=head_dim, lanes=shape[2])
    assert sparse_lm.head_norm_why_not(shape[1], shape[2],
                                       head_dim) is not None
    for fn, ref in ((now, today),
                    (jax.grad(lambda *a: jnp.sum(now(*a) * w), (0, 1)),
                     jax.grad(lambda *a: jnp.sum(today(*a) * w), (0, 1)))):
        for a, b in zip(jax.tree.leaves(jax.jit(fn)(x, scale)),
                        jax.tree.leaves(jax.jit(ref)(x, scale))):
            assert a.dtype == b.dtype and np.array_equal(a, b)
