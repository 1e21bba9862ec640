"""The blockwise causal attention kernels (ops/pallas/
causal_attention_kernels.py), interpreted on the CPU, against dense masked
attention: full and windowed, 7 query heads and 1 to a key-value head, a
length that is not a multiple of the block; and the list of tiles they
visit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.models.sparse_lm import dense_causal_attention
from dalle_tpu.ops.pallas import causal_attention_kernels as K


def _operands(t, group, kv_heads, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    wide, narrow = kv_heads * group * K.LANES, kv_heads * K.LANES
    return (jax.random.normal(keys[0], (2, t, wide)),
            jax.random.normal(keys[1], (2, t, narrow)),
            jax.random.normal(keys[2], (2, t, narrow)),
            jax.random.normal(keys[3], (2, t, wide)))


@pytest.mark.parametrize("t, group, kv_heads, window", [
    (300, 7, 1, None),      # full causal, 7 to a key-value head, ragged T
    (300, 7, 1, 160),       # a window that crosses block edges
    (384, 1, 2, 130),       # every head its own key-value head
    (256, 2, 2, None),
])
def test_blockwise_attention_matches_dense(t, group, kv_heads, window):
    q, k, v, w = _operands(t, group, kv_heads)

    def blockwise(q, k, v):
        return K.causal_attention(q, k, v, window, 128, True)

    def dense(q, k, v):
        return dense_causal_attention(q, k, v, window, K.LANES)

    np.testing.assert_allclose(blockwise(q, k, v), dense(q, k, v),
                               atol=5e-6)
    got = jax.grad(lambda *a: jnp.sum(blockwise(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert float(jnp.abs(g - r).max() / jnp.abs(r).max()) < 1e-5


def test_only_the_tiles_inside_the_band_are_visited():
    """At the benchmark's length a causal layer visits the lower triangle
    of 16 x 16 tiles and a 4096 window about 3/4 of that; every query and
    every key block is visited, runs are marked at both ends, and both
    orders hold the same pairs."""
    full = K.band_pairs(16, 512, None, key_major=False)
    assert len(full) == 16 * 17 // 2
    windowed = K.band_pairs(16, 512, 4096, key_major=False)
    assert len(windowed) == 108 and 0.7 < len(windowed) / len(full) < 0.8
    by_key = K.band_pairs(16, 512, 4096, key_major=True)
    assert {tuple(r[:2]) for r in windowed} == {tuple(r[:2]) for r in by_key}
    for table, major in ((windowed, 0), (by_key, 1)):
        assert set(table[:, major]) == set(range(16))
        assert table[:, 2].sum() == table[:, 3].sum() == 16
        assert np.all(np.diff(table[:, major]) >= 0)
    # a tile is inside the band iff it holds an allowed (query, key) pair
    i = np.arange(16 * 512)
    allowed = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < 4096)
    tiles = allowed.reshape(16, 512, 16, 512).any(axis=(1, 3))
    assert {tuple(r[:2]) for r in windowed} == set(zip(*np.nonzero(tiles)))


@pytest.mark.parametrize("q_width, kv_width, head_dim, fits", [
    (28 * 128, 4 * 128, 128, True), (4 * 64, 2 * 64, 64, False),
    (3 * 128, 2 * 128, 128, False)])
def test_shapes_the_kernels_take(q_width, kv_width, head_dim, fits):
    assert (K.blockwise_fits(q_width, kv_width, head_dim) is None) == fits
