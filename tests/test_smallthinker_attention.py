"""The blockwise causal attention kernels (ops/pallas/
causal_attention_kernels.py), interpreted on the CPU, against dense masked
attention: full and windowed, 7 query heads and 1 to a key-value head, a
length that is not a multiple of the block; the backward as one kernel a
tile and as the ``dq`` + ``dk``/``dv`` pair it is where the accumulators
do not fit; the list of tiles they visit, and the sub-tiles of an edge tile
that they multiply."""
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
    return jax.jit(jax.grad(weighed, (0, 1, 2)))(q, k, v)


@pytest.fixture
def sub_tiles(monkeypatch):
    """``sub_tiles(block, sub)``: the calls cut an edge tile of ``block``
    into sub-tiles of ``sub`` (``sub`` = ``block``: whole tiles)."""
    def cut(block, sub):
        monkeypatch.setattr(K, "SUB_BLOCK", sub)
        assert K.sub_block(block) == sub
    return cut


@pytest.fixture
def kernels_called(monkeypatch):
    """The names of the kernels ``_call`` was handed, in order; each with
    the sub-tile the call's record states (``band_of``)."""
    called, real = [], K._call

    def spy(kernel, *args, **kw):
        called.append(kernel.__name__)
        said = K.band_of(kw["t"], kw["window"], block=kw["block"])
        assert kw["sub"] == said["sub"]
        return real(kernel, *args, **kw)
    monkeypatch.setattr(K, "_call", spy)
    return called


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("t, group, kv_heads, window, block, sub, dtype", [
    # full causal, 7 to a key-value head, ragged T
    (300, 7, 1, None, 128, 128, jnp.float32),
    # a window that crosses block edges
    (300, 7, 1, 160, 128, 128, jnp.float32),
    # every head its own key-value head
    (384, 1, 2, 130, 128, 128, jnp.float32),
    (256, 2, 2, None, 128, 128, jnp.float32),
    # five and six blocks: a key block is summed into from several query
    # blocks' runs, and a window of 200 cuts through the blocks' edges
    (640, 7, 1, 200, 128, 128, jnp.float32),
    (640, 8, 1, None, 128, 128, jnp.float32),
    (700, 8, 1, 200, 128, 128, jnp.float32),     # ragged as well
    (700, 7, 1, None, 128, 128, jnp.float32),
    # tiles of 256 in sub-tiles of 128, every kind of tile: diagonal and
    # interior tiles, the last one ragged
    (600, 2, 1, None, 256, 128, jnp.float32),
    # a window's far edge that is a multiple of the block (one kind of edge
    # tile beside the diagonal), interior tiles between the two; two
    # key-value heads, so that the next head's and the next sample's runs
    # start from accumulators an edge tile's strips summed into
    (1024, 1, 2, 512, 256, 128, jnp.float32),
    (768, 2, 1, 512, 256, 128, jnp.bfloat16),
    # one that is not (two kinds), ragged
    (700, 2, 1, 300, 256, 128, jnp.float32),
    (700, 1, 1, 300, 256, 128, jnp.bfloat16),
    # a window narrower than one sub-tile: a far-edge tile keeps one
    # sub-tile of its four, and a query sub-block of it keeps none
    (512, 1, 2, 100, 256, 128, jnp.float32),
    # the sizes the kernels ship with, tiles of 512 in sub-tiles of 256: a
    # window off both, ragged
    (1100, 1, 1, 700, K.BLOCK, K.SUB_BLOCK, jnp.float32),
    # 16 query heads a key-value tile (32 over 2, ``twotower30b``'s), ragged
    (300, 16, 2, None, 128, 128, jnp.float32),
])
def test_blockwise_attention_matches_dense(t, group, kv_heads, window, block,
                                           sub, dtype, kernels_called,
                                           sub_tiles):
    q, k, v, w = _operands(t, group, kv_heads, dtype=dtype)     # 2 samples
    w = w.astype(jnp.float32)
    exact = dtype == jnp.float32
    sub_tiles(block, sub)

    def blockwise(q, k, v):
        return K.causal_attention(q, k, v, window, block, True)

    def dense(q, k, v):
        # of the same operands, in f32: what bf16 operands round is the
        # kernels' probabilities and the results
        return dense_causal_attention(*(x.astype(jnp.float32)
                                        for x in (q, k, v)), window, K.LANES)

    def out_and_grads(attention):
        def weighed(q, k, v):
            out = attention(q, k, v).astype(jnp.float32)
            return jnp.sum(out * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            weighed, (0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    (out, got), (ref, want) = out_and_grads(blockwise), out_and_grads(dense)
    np.testing.assert_allclose(out, ref, atol=5e-6 if exact else 2e-2)
    for g, r in zip(got, want):
        assert g.dtype == dtype
        assert _rel(g, r) < (1e-5 if exact else 2e-2)
    # the backward was the one kernel
    assert set(kernels_called) == {"_causal_fwd_kernel",
                                   "_causal_bwd_kernel"}


@pytest.mark.parametrize("t, group, window, block, sub, dtype, limit", [
    (640, 7, 200, 128, 128, jnp.float32, 1e-6),
    (700, 8, None, 128, 128, jnp.float32, 1e-6),
    (700, 7, 200, 128, 128, jnp.float32, 1e-6),
    # bfloat16 operands, as the cells': one rounding of the widest value
    (640, 8, 200, 128, 128, jnp.bfloat16, 2 ** -8),
    # tiles of 256 in sub-tiles of 128: the diagonal, a far edge on the
    # block's multiple and one off it, a window inside one sub-tile, ragged
    (600, 2, None, 256, 128, jnp.float32, 1e-6),
    (768, 2, 512, 256, 128, jnp.float32, 1e-6),
    (700, 2, 300, 256, 128, jnp.bfloat16, 2 ** -8),
    (700, 1, 100, 256, 128, jnp.float32, 1e-6),
    # the sizes the kernels ship with
    (1100, 1, 700, K.BLOCK, K.SUB_BLOCK, jnp.float32, 1e-6),
    # 16 query heads a key-value tile
    (300, 16, None, 128, 128, jnp.float32, 1e-6),
])
def test_the_split_backward_agrees_with_the_fused(t, group, window, block,
                                                  sub, dtype, limit,
                                                  kernels_called, sub_tiles,
                                                  monkeypatch):
    """Past the length whose ``dk`` / ``dv`` fit VMEM the backward is the
    ``dq`` and the ``dk``/``dv`` kernel: reached here by shrinking the
    budget, and held to the one kernel's result, which sums in the same
    order: an edge tile's sub-tiles too, which the three kernels cut by one
    helper."""
    q, k, v, w = _operands(t, group, 1, seed=3, dtype=dtype, batch=1)
    sub_tiles(block, sub)
    fused = _grads(q, k, v, w, window, block)
    assert kernels_called == ["_causal_fwd_kernel", "_causal_bwd_kernel"]
    del kernels_called[:]
    monkeypatch.setattr(K, "VMEM_LIMIT_BYTES", 512 * 1024)
    assert K.fused_backward_fits(t, group, q.dtype.itemsize, block)
    split = _grads(q, k, v, w, window, block)
    assert kernels_called == ["_causal_fwd_kernel", "_causal_dq_kernel",
                              "_causal_dkv_kernel"]
    for got, want in zip(split, fused):
        assert got.dtype == want.dtype == dtype
        assert _rel(got, want) <= limit


@pytest.mark.parametrize("tokens, group, itemsize, fits", [
    (8192, 7, 2, True),         # smallthinker21b's layers
    (8192, 8, 2, True),         # trinitymini's
    (8192, 8, 4, True),
    (8192, 16, 2, True),        # twotower30b's: 16 query heads a tile
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


@pytest.mark.parametrize("window", [
    None, 128, 256,         # full causal; one sub-tile of 128, of 256
    1024, 2048, 4096,       # multiples of both blocks (the cells' two)
    200, 1500])             # multiples of neither block nor sub-tile
@pytest.mark.parametrize("block, sub", [(256, 128), (256, 256), (512, 128),
                                        (512, 256)])
def test_an_edge_tiles_sub_tiles_are_those_that_hold_an_allowed_pair(
        block, sub, window):
    """Against brute force over every ``d`` = query block - key block of a
    16-block band: a tile the function does not list holds allowed pairs
    only (or, outside the band, none); of one it lists, every sub-tile it
    keeps holds an allowed pair and every one it drops holds none; the tiles
    of ``band_pairs`` are those and no others."""
    edges = K.edge_tiles(block, window, sub)
    n = block // sub
    at = np.arange(block)
    band = {r[0] - r[1] for r in K.band_pairs(16, block, window, False)}
    for d in range(16):
        rel = d * block + at[:, None] - at[None, :]
        allowed = (rel >= 0) & (True if window is None else rel < window)
        assert (d in band) == bool(allowed.any())
        if d in edges:
            assert d in band and not allowed.all()
            held = allowed.reshape(n, sub, n, sub).any(axis=(1, 3))
            assert edges[d].dtype == bool
            np.testing.assert_array_equal(edges[d], held)
            # a query sub-block's kept key sub-blocks lie side by side
            for rows, keys in K._strips(edges[d], sub):
                assert allowed[rows, keys].any(axis=1).any()
                assert not np.delete(allowed[rows], np.r_[keys], 1).any()
        else:
            assert allowed.all() or d not in band
    assert 0 in edges and len(edges) <= 3


@pytest.mark.parametrize("layers, whole, by_256, by_128, tiles, edge", [
    # smallthinker21b: 1 full layer and 3 of window 4096
    ([(None, 1), (4096, 3)], 1.1057, 1.0528, 1.0263, 460, 88),
    # trinitymini: 4 layers of window 2048 and 1 full
    ([(2048, 4), (None, 1)], 1.1817, 1.0908, 1.0454, 416, 128),
    # a full layer alone (joyaiflash, lfm2moe, keyevl2: whole tiles still)
    ([(None, 1)], 1.0624, 1.0311, 1.0155, 136, 16),
])
def test_visited_over_allowed_pairs_at_the_cells_shapes(
        layers, whole, by_256, by_128, tiles, edge):
    """What the band's granularity costs a whole step's attention layers at
    T = 8192 in tiles of 512, and what sub-tiles of an edge tile leave of
    it: the account the engagement sentence states."""
    def over(sub):
        accounts = [(n, K.band_account(16, 512, window, sub))
                    for window, n in layers]
        total = lambda key: sum(n * a[key] for n, a in accounts)
        assert total("whole") == total("tiles") * 512 * 512
        assert (total("tiles"), total("edge_tiles")) == (tiles, edge)
        return total("visited") / total("allowed")
    assert over(512) == pytest.approx(whole, abs=5e-5)
    assert over(256) == pytest.approx(by_256, abs=5e-5)
    assert over(128) == pytest.approx(by_128, abs=5e-5)
    # the constant the kernels ship with, and who takes it
    assert K.sub_block(K.BLOCK) == K.SUB_BLOCK == 256
    assert K.sub_block(K.BLOCK, K.HALF) == K.BLOCK
    # and the account a call's record states: the padded length's, at them
    for window, _ in layers:
        assert K.band_of(8192 - 100, window) == K.band_account(
            16, K.BLOCK, window, K.SUB_BLOCK)
        assert K.band_of(8192, window, K.HALF) == K.band_account(
            16, K.BLOCK, window, K.BLOCK)


@pytest.mark.parametrize("q_width, kv_width, head_dim, fits", [
    (28 * 128, 4 * 128, 128, True), (4 * 32, 4 * 32, 32, False),
    (3 * 128, 2 * 128, 128, False)])
def test_shapes_the_kernels_take(q_width, kv_width, head_dim, fits):
    assert (K.blockwise_fits(q_width, kv_width, head_dim) is None) == fits
