"""The blockwise causal attention kernels (ops/pallas/
causal_attention_kernels.py), interpreted on the CPU, against dense masked
attention: full and windowed, 7 query heads and 1 to a key-value head, a
length that is not a multiple of the block; the backward as one kernel a
tile and as the ``dq`` + ``dk``/``dv`` pair it is where the accumulators
do not fit; and the list of tiles they visit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.models.sparse_lm import dense_causal_attention
from dalle_tpu.ops.pallas import causal_attention_kernels as K


def _operands(t, group, kv_heads, seed=0, dtype=jnp.float32, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    wide, narrow = kv_heads * group * K.LANES, kv_heads * K.LANES
    return (jax.random.normal(keys[0], (batch, t, wide), dtype),
            jax.random.normal(keys[1], (batch, t, narrow), dtype),
            jax.random.normal(keys[2], (batch, t, narrow), dtype),
            jax.random.normal(keys[3], (batch, t, wide), dtype))


def _grads(q, k, v, w, window, block=128):
    def weighed(q, k, v):
        out = K.causal_attention(q, k, v, window, block, True)
        return jnp.sum(out.astype(jnp.float32) * w)
    return jax.grad(weighed, (0, 1, 2))(q, k, v)


@pytest.fixture
def kernels_called(monkeypatch):
    """The names of the kernels ``_call`` was handed, in order."""
    called, real = [], K._call

    def spy(kernel, *args, **kw):
        called.append(kernel.__name__)
        return real(kernel, *args, **kw)
    monkeypatch.setattr(K, "_call", spy)
    return called


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("t, group, kv_heads, window", [
    (300, 7, 1, None),      # full causal, 7 to a key-value head, ragged T
    (300, 7, 1, 160),       # a window that crosses block edges
    (384, 1, 2, 130),       # every head its own key-value head
    (256, 2, 2, None),
    # five and six blocks: a key block is summed into from several query
    # blocks' runs, and a window of 200 cuts through the blocks' edges
    (640, 7, 1, 200),
    (640, 8, 1, None),
    (700, 8, 1, 200),       # ragged as well
    (700, 7, 1, None),
])
def test_blockwise_attention_matches_dense(t, group, kv_heads, window,
                                           kernels_called):
    q, k, v, w = _operands(t, group, kv_heads)

    def blockwise(q, k, v):
        return K.causal_attention(q, k, v, window, 128, True)

    def dense(q, k, v):
        return dense_causal_attention(q, k, v, window, K.LANES)

    np.testing.assert_allclose(blockwise(q, k, v), dense(q, k, v),
                               atol=5e-6)
    got = _grads(q, k, v, w, window)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert _rel(g, r) < 1e-5
    # the backward was the one kernel
    assert set(kernels_called) == {"_causal_fwd_kernel",
                                   "_causal_bwd_kernel"}


@pytest.mark.parametrize("t, group, window, dtype, limit", [
    (640, 7, 200, jnp.float32, 1e-6),
    (700, 8, None, jnp.float32, 1e-6),
    (700, 7, 200, jnp.float32, 1e-6),
    # bfloat16 operands, as the cells': one rounding of the widest value
    (640, 8, 200, jnp.bfloat16, 2 ** -8),
])
def test_the_split_backward_agrees_with_the_fused(t, group, window, dtype,
                                                  limit, kernels_called,
                                                  monkeypatch):
    """Past the length whose ``dk`` / ``dv`` fit VMEM the backward is the
    ``dq`` and the ``dk``/``dv`` kernel: reached here by shrinking the
    budget, and held to the one kernel's result, which sums in the same
    order."""
    q, k, v, w = _operands(t, group, 1, seed=3, dtype=dtype, batch=1)
    fused = _grads(q, k, v, w, window)
    assert kernels_called == ["_causal_fwd_kernel", "_causal_bwd_kernel"]
    del kernels_called[:]
    monkeypatch.setattr(K, "VMEM_LIMIT_BYTES", 512 * 1024)
    assert K.fused_backward_fits(t, group, q.dtype.itemsize, 128)
    split = _grads(q, k, v, w, window)
    assert kernels_called == ["_causal_fwd_kernel", "_causal_dq_kernel",
                              "_causal_dkv_kernel"]
    for got, want in zip(split, fused):
        assert got.dtype == want.dtype == dtype
        assert _rel(got, want) <= limit


@pytest.mark.parametrize("tokens, group, itemsize, fits", [
    (8192, 7, 2, True),         # smallthinker21b's layers
    (8192, 8, 2, True),         # trinitymini's
    (8192, 8, 4, True),
    (8000, 8, 2, True),         # judged at the padded length
    (25600, 8, 2, True),        # the longest the compiler takes as well
    (26112, 8, 2, False),
    (32768, 7, 2, False),
    (16384, 8, 4, False),
])
def test_where_the_fused_backward_fits(tokens, group, itemsize, fits):
    """A pure function of the local shapes against the VMEM every call
    asks for: (T, 128) f32 accumulators of ``dk`` and ``dv`` and their
    outputs grow with T, the tiles do not."""
    why_not = K.fused_backward_fits(tokens, group, itemsize)
    assert (why_not is None) == fits
    if not fits:
        padded = tokens + -tokens % K.BLOCK
        assert why_not.startswith(f"dk and dv of {padded} tokens need ")
        assert why_not.endswith(" MiB of VMEM, over 64")


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
    (28 * 128, 4 * 128, 128, True), (4 * 32, 4 * 32, 32, False),
    (3 * 128, 2 * 128, 128, False)])
def test_shapes_the_kernels_take(q_width, kv_width, head_dim, fits):
    assert (K.blockwise_fits(q_width, kv_width, head_dim) is None) == fits
