"""The Mamba-2 chunked scan's kernel pair (ops/pallas/ssm_scan_kernels.py),
interpreted on the CPU, against the XLA lowering of the same site
(``sparse_lm.chunked_scan``) and the yardstick's token-by-token recurrence:
``y`` and every operand's gradient through a rematerialised call (the
forward, its replay and the backward), one chunk and many, one head a group
and several, heads of half a lane tile and of a whole one, two samples, f32
and bfloat16; the site's predicate, each refusal with its recorded reason
and the XLA lowering's result; per shard on the 8-device mesh."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.config import NemotronHLMConfig
from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import ssm_scan_kernels as K
from dalle_tpu.parallel.mesh import make_mesh
from sparse_family import rel_l2

Y = Manifest().yardstick("nemotronh")
OPERANDS = ("x", "B", "C", "dt", "a", "d")

# samples, tokens, heads, head width, groups, state, chunk
SHAPES = {
    "one_chunk_one_head_a_group": (1, 128, 2, 128, 2, 128, 128),
    "many_chunks_four_heads_a_group": (2, 384, 4, 64, 1, 128, 128),
    "two_steps_two_heads_a_group": (2, 512, 4, 64, 2, 128, 128),
}
# tokens a grid step where not the kernels' own: the state, and its
# cotangent, carried from one grid step to the next
STEP_TOKENS = {"two_steps_two_heads_a_group": 256}


def _operands(shape, dtype, seed=0):
    b, t, h, p, g, n, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 7)
    return (jax.random.normal(keys[0], (b, t, h * p)).astype(dtype),
            jax.random.normal(keys[1], (b, t, g * n)).astype(dtype),
            jax.random.normal(keys[2], (b, t, g * n)).astype(dtype),
            jax.nn.softplus(jax.random.normal(keys[3], (b, t, h)) - 2.0),
            -jnp.exp(jax.random.normal(keys[4], (h,))),
            jax.random.normal(keys[5], (h,)),
            jax.random.normal(keys[6], (b, t, h * p)))


def _value_and_grads(scan, operands):
    """``y`` and the gradient of every operand through a rematerialised
    call of ``scan``."""
    *operands, weigh = operands

    def loss(*o):
        y = jax.checkpoint(scan)(*o)
        return jnp.sum(y.astype(jnp.float32) * weigh), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(OPERANDS))), has_aux=True))(*operands)
    return (y,) + grads


def _by_token(shape):
    _, _, h, _, g, _, _ = shape

    def scan(x, bm, cm, dt, a, d):
        b, t = x.shape[:2]
        return Y.recurrence(
            x.reshape(b, t, g, h // g, -1), bm.reshape(b, t, g, -1),
            cm.reshape(b, t, g, -1), dt.reshape(b, t, g, -1),
            a.reshape(g, -1), d.reshape(g, -1)).reshape(x.shape)
    return scan


@functools.lru_cache(maxsize=None)
def _three(name, dtype):
    """(kernels, XLA lowering, recurrence in f32), each ``y`` and the six
    gradients."""
    shape = SHAPES[name]
    _, _, h, _, g, _, chunk = shape
    operands = _operands(shape, dtype)
    sizes = dict(heads=h, groups=g, chunk=chunk)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(K, "STEP_TOKENS", STEP_TOKENS.get(name, K.STEP_TOKENS))
        kernels = _value_and_grads(
            functools.partial(K.scan, **sizes, interpret=True), operands)
    xla = _value_and_grads(
        functools.partial(sparse_lm.chunked_scan, **sizes), operands)
    exact = tuple(o.astype(jnp.float32) for o in operands)
    return kernels, xla, _value_and_grads(_by_token(shape), exact)


@pytest.mark.parametrize("name", list(SHAPES))
def test_the_kernels_are_the_chunked_scan_and_the_recurrence_f32(name):
    assert K.fits(*SHAPES[name][1:], 4) is None
    kernels, xla, by_token = _three(name, "float32")
    for what, got, want, true in zip(("y",) + OPERANDS, kernels, xla,
                                     by_token):
        assert got.shape == want.shape and got.dtype == want.dtype, what
        assert rel_l2(got, want) < 1e-5, what
        assert rel_l2(got, true) < 2e-5, what


@pytest.mark.parametrize("name", list(SHAPES))
def test_in_bfloat16_the_kernels_lie_as_near_the_recurrence_as_the_xla_code(
        name):
    """bfloat16 operands: each result no further from the f32 recurrence
    on the same numbers than the XLA lowering's own distance (half as far
    again, or a bfloat16 step of 2^-8 where that is less)."""
    kernels, xla, by_token = _three(name, "bfloat16")
    for what, got, want, true in zip(("y",) + OPERANDS, kernels, xla,
                                     by_token):
        assert got.dtype == want.dtype, what
        assert rel_l2(got, true) < max(1.5 * rel_l2(want, true), 2 ** -8), (
            what, rel_l2(got, true), rel_l2(want, true))


def test_a_steps_chunks_divide_the_sample_and_fit_vmem(monkeypatch):
    sizes = (8, 64, 128, 2)         # heads a group, their width, state, bf16
    assert K.chunks_a_step(64, 128, *sizes) == 16
    assert K.chunks_a_step(3, 128, *sizes) == 3
    assert K.chunks_a_step(34, 128, *sizes) == 2
    assert K.vmem_bytes(128, *sizes, 16) < K._VMEM < K.vmem_bytes(
        128, 16, 128, 256, 2, 16)
    assert K.chunks_a_step(64, 128, 16, 128, 256, 2) == 4
    monkeypatch.setattr(K, "STEP_TOKENS", 256)
    assert K.chunks_a_step(4, 128, *sizes) == 2     # two grid steps of two
    assert K.chunks_a_step(7, 512, *sizes) == 1


TINY = dict(hidden_size=64, num_hidden_layers=1, layer_kinds=("mamba2",),
            num_heads=4, num_kv_heads=2, head_dim=16, vocab_size=96,
            text_seq_len=27, image_grid=4, vocab_text=48, vocab_image=48,
            dtype="float32")
FITS = dict(mamba_num_heads=4, mamba_head_dim=64, ssm_groups=2,
            ssm_state_size=128, ssm_chunk=128)


@pytest.mark.parametrize("tokens, sizes, why", [
    (256, {}, None),
    (200, {}, "200 tokens are not whole chunks of 128"),
    (128, dict(ssm_chunk=64), "a chunk of 64 is not whole 128-lane tiles"),
    (128, dict(ssm_state_size=64),
     "a state of 64 is not whole 128-lane tiles"),
    (128, dict(mamba_head_dim=96),
     "heads of 96 lanes are neither whole 128-lane tiles nor whole heads a "
     "tile"),
    (128, dict(mamba_head_dim=32),
     "a group's 2 heads of 32 are not whole 128-lane tiles"),
    # the tests' tiny model
    (43, dict(mamba_head_dim=8, ssm_state_size=16, ssm_chunk=8),
     "43 tokens are not whole chunks of 8"),
    (40, dict(mamba_head_dim=8, ssm_state_size=16, ssm_chunk=8),
     "a chunk of 8 is not whole 128-lane tiles"),
])
def test_the_site_takes_the_kernels_or_says_why_not(tokens, sizes, why,
                                                    monkeypatch,
                                                    lowering_record):
    """The predicate on the local shapes, through the site: the record
    holds the reason (or the kernels' facts), and a refused call is the XLA
    lowering's result bit for bit."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = NemotronHLMConfig(**dict(TINY, **dict(FITS, **sizes)))
    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    g, n, chunk = cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_chunk
    *operands, _ = _operands((1, tokens, h, p, g, n, chunk), "float32")
    got = jax.jit(functools.partial(sparse_lm.ssm_scan, mesh=None, cfg=cfg))(
        *operands)
    want = jax.jit(functools.partial(sparse_lm.chunked_scan, heads=h,
                                     groups=g, chunk=chunk))(*operands)
    key = sparse_lm._scan_key(tokens, cfg)
    assert key == (tokens, h, p, g, n, chunk)
    said = lowering_record.recorded(sparse_lm.SCAN_SITE, key)
    assert lowering_record.why_not(sparse_lm.SCAN_SITE, key) == why
    if why is None:
        assert said == {"why_not": None, "chunks_a_step": 2,
                        "backward": sparse_lm.SCAN_BACKWARD}
        assert rel_l2(got, want) < 1e-5
    else:
        assert said == {"why_not": why}
        np.testing.assert_array_equal(got, want)


def test_blocks_that_pass_vmem_or_a_tiles_rows_are_refused(monkeypatch):
    assert K.fits(8192, 64, 64, 8, 128, 128, 2) is None      # the cell's
    assert K.fits(8192, 128, 64, 1, 128, 128, 2) == (
        "128 heads a group pass 64 rows of a tile")
    monkeypatch.setattr(K, "_VMEM", 1 << 20)
    assert K.fits(8192, 64, 64, 8, 128, 128, 2).startswith(
        "a chunk of 128 x 512 and a state of 512 x 128 need ")


@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_a_shards_samples_and_the_vectors_of_one_device(
        nested, monkeypatch, inside_manual_dp, lowering_record):
    """dp 2 x fsdp 2 x tp 2: a shard holds one of the four samples, every
    head of it (no axis splits the mixer's lanes); the replicated ``a`` and
    ``d`` get the one-device gradient with no sum written out."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    cfg = NemotronHLMConfig(**dict(TINY, **FITS))
    *operands, weigh = _operands((4, 128, 4, 64, 2, 128, 128), "float32")

    def value_and_grads(mesh_):
        def f(a, d, x, bm, cm, dt, w):
            y = sparse_lm.ssm_scan(x, bm, cm, dt, a, d, mesh=mesh_, cfg=cfg,
                                   scope="scan")
            return jnp.sum(y * w), y
        vg = jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (False, False) + (True,) * 5,
                                  tuple(range(6)))
        return jax.jit(vg)

    x, bm, cm, dt, a, d = operands
    (_, y_m), g_m = value_and_grads(mesh)(a, d, x, bm, cm, dt, weigh)
    (_, y_1), g_1 = value_and_grads(None)(a, d, x, bm, cm, dt, weigh)
    assert len(y_m.sharding.device_set) == 8
    assert lowering_record.why_not(
        sparse_lm.SCAN_SITE, sparse_lm._scan_key(128, cfg)) is None
    np.testing.assert_allclose(y_m, y_1, rtol=1e-6, atol=1e-6)
    for name, got, want in zip(("a", "d", "x", "B", "C", "dt"), g_m, g_1):
        assert rel_l2(got, want) < 1e-6, name
