"""The one-pass head norm (ops/pallas/head_norm_kernels.py), interpreted on
the CPU, against the expression it replaces (a reshape to heads and
``rms_norm``) and its ``jax.grad``: 32- and 4-head widths, bf16 and f32, a
row count that takes more than one tile, two samples, heads of two lane
tiles; per shard on the 8-device mesh; and the rule that chooses it. The
same pass with the rotary in it, after the norm (``trinitymini``) or alone
(``smallthinker21b``), against the two expressions it replaces: the
norm's pass and ``apply_rotary_lanes`` with tables as wide as the array.
And the pass of interleaved pairs (``joyaiflash``'s latent attention:
``pair_rotary``) against ``rotary_interleaved_lanes``: the queries' rotary
columns read out of ``q_b``'s wider array, the one 64-wide key."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import head_norm_kernels as K
from dalle_tpu.parallel.mesh import LANES_SPEC, make_mesh
from sparse_family import rel_l2


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


@pytest.mark.parametrize("variant", ["norm", "norm_rotary", "rotary"])
@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_whole_heads_and_dscale_of_one_device(nested, variant,
                                                        monkeypatch,
                                                        inside_manual_dp,
                                                        lowering_record):
    """dp 2 x fsdp 2 x tp 2: a shard holds a sample's rows of two of the
    four heads; the replicated scale's gradient is the one-device value
    with no sum written out (the ``shard_map``'s transpose sums it over
    the axes it binds, the gradient accumulation's psum over ``dp``). One
    head's tables are every head's: a shard needs no other."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (4, 24, 512)) * 2.0
    w = jax.random.normal(keys[1], x.shape)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (128,))
    normed, rotated = "norm" in variant, "rotary" in variant

    def value_and_grads(mesh_):
        def f(scale, x, w):
            out = sparse_lm.head_pass(
                x, scale if normed else None, mesh=mesh_, eps=EPS,
                head_dim=128, theta=1e4 if rotated else None)
            return jnp.sum(out * w), out
        vg = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (False, True, True), (0, 1))
        return jax.jit(vg)

    (_, out_m), g_m = value_and_grads(mesh)(scale, x, w)
    (_, out_1), g_1 = value_and_grads(None)(scale, x, w)
    assert len(out_m.sharding.device_set) == 8
    # a tp shard's two heads of the four took the pass
    assert lowering_record.why_not(
        " + ".join(["head norm"] * normed + ["rotary"] * rotated),
        (24, 256, 128)) is None
    np.testing.assert_allclose(out_m, out_1, rtol=1e-6, atol=1e-6)
    # (no gradient reaches a scale that is not there)
    for a, b in list(zip(g_m, g_1))[1 - normed:]:
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
                           monkeypatch, lowering_record):
    """The rule's answer on the local shapes; with no Mosaic backend the
    record answers for it, whatever was traced."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    assert lowering_record.why_not("rotary", (tokens, width, head_dim)) == (
        "none traced" if interpret else why)
    if interpret:
        assert K.fits(tokens, width, head_dim) == why


@pytest.mark.parametrize("variant", ["norm", "norm_rotary", "rotary"])
@pytest.mark.parametrize("shape, head_dim", [((2, 32, 256), 64),
                                             ((1, 12, 256), 128)])
def test_what_does_not_fit_is_todays_expression_bit_for_bit(shape, head_dim,
                                                            variant,
                                                            monkeypatch):
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], shape).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], shape).astype(jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(keys[2], (head_dim,))
    normed, rotated = "norm" in variant, "rotary" in variant

    def today(x, scale):
        if normed:
            x = by_reshape(x, scale, head_dim)
        if rotated:
            x = by_rotary_lanes(x, head_dim, 1e4)
        return x

    def now(x, scale):
        return sparse_lm.head_pass(
            x, scale if normed else None, mesh=None, eps=EPS,
            head_dim=head_dim, theta=1e4 if rotated else None)

    assert K.fits(shape[1], shape[2], head_dim) is not None
    for fn, ref in ((now, today),
                    (jax.grad(lambda *a: jnp.sum(now(*a) * w), (0, 1)),
                     jax.grad(lambda *a: jnp.sum(today(*a) * w), (0, 1)))):
        for a, b in zip(jax.tree.leaves(jax.jit(fn)(x, scale)),
                        jax.tree.leaves(jax.jit(ref)(x, scale))):
            assert a.dtype == b.dtype and np.array_equal(a, b)


# ---------------------------------------------------------------------------
# The rotary in the pass
# ---------------------------------------------------------------------------

# what the pass does beside the rotary, and the configuration's theta
VARIANTS = {"norm_rotary": (True, 1e4),      # trinitymini
            "rotary": (False, 1.5e6)}        # smallthinker21b

# heads, head_dim, (samples, rows), dtype: queries' and keys' widths of the
# two configurations, each over more than one tile of rows
ROTARY_CASES = {
    "q_4096_lanes_bf16": (32, 128, (1, 512), "bfloat16"),
    "q_3584_lanes_two_samples_bf16": (28, 128, (2, 512), "bfloat16"),
    "k_512_lanes_bf16": (4, 128, (1, 4096), "bfloat16"),
    "k_512_lanes_one_tile_f32": (4, 128, (2, 104), "float32"),
    "2_heads_of_256_f32": (2, 256, (1, 64), "float32"),
}


def by_rotary_lanes(x, head_dim, theta):
    """The expression the pass replaces: tables as wide as the array."""
    return attention.apply_rotary_lanes(
        x, *attention.rotary_cos_sin(jnp.arange(x.shape[1]), head_dim,
                                     theta, x.shape[2] // head_dim),
        head_dim)


def as_stated(fn, *args):
    """``fn(*args)`` compiled to round where the program says it does. Left
    to itself the CPU's compiler, which runs an interpreted kernel too,
    keeps a value that is cast to bf16 and back in f32
    (``xla_allow_excess_precision``); the chip's kernel compiler rounds."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


@functools.lru_cache(maxsize=None)
def _rotated(variant, case):
    """(out, dx, dscale) of the one pass, of the two passes it replaces
    (the norm's kernel, then the XLA rotary), and of the plain expression
    in f32 from the same input (``dx`` rounded to the input's dtype as a
    gradient is); ``dscale`` None where there is no norm."""
    normed, theta = VARIANTS[variant]
    heads, head_dim, rows, dtype = ROTARY_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    shape = (*rows, heads * head_dim)
    x = (jax.random.normal(keys[0], shape) * 2.0 + 0.3).astype(dtype)
    w = jax.random.normal(keys[1], shape).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(keys[2], (head_dim,))

    # the tables of both sides from one compiled cosine: fused into its
    # reader, the CPU's compiler emits another one in some fusions, a last
    # digit apart at large angles
    pos = jnp.arange(rows[1])
    wide = attention.rotary_cos_sin(pos, head_dim, theta, heads)
    one = attention.rotary_cos_sin(pos, head_dim, theta)
    if dtype == "bfloat16":
        # ... and of 8 significant bits beside a bf16 input's 8, so that
        # both products are exact in f32 and a fused multiply-add, which
        # the CPU's compiler makes of one product or the other as it likes
        # (the v5e's vector unit has none), rounds as the two operations do
        wide, one = (tuple(t.astype(dtype).astype(jnp.float32) for t in ts)
                     for ts in (wide, one))
    assert all(np.array_equal(a, b[:, -head_dim:]) for a, b in zip(one, wide))
    tables = K.rotary_tables(*one)

    def one_pass(x, scale):
        return K.per_head(x, scale if normed else None, tables, EPS,
                          head_dim, True)

    def two_passes(x, scale):
        if normed:
            x = K.head_rms_norm(x, scale, EPS, head_dim, True)
        return attention.apply_rotary_lanes(x, *wide, head_dim)

    def plain_f32(x, scale):
        x = x.astype(jnp.float32)
        if normed:
            x = by_reshape(x, scale, head_dim)
        return attention.apply_rotary_lanes(x, *wide, head_dim)

    def outputs(fn, w):
        def run(x, scale, w):
            y, vjp = jax.vjp(fn, x, scale)
            dx, dscale = vjp(w)
            return y, dx, dscale if normed else None
        return as_stated(run, x, scale, w)

    return (outputs(one_pass, w), outputs(two_passes, w),
            outputs(plain_f32, w.astype(jnp.float32)))


ROTATED = [(v, c) for v in VARIANTS for c in ROTARY_CASES]


@pytest.mark.parametrize("variant, case", ROTATED)
def test_forward_is_the_two_passes_bit_for_bit(variant, case):
    """The same f32 arithmetic a head and the same roundings: the normed
    value to ``x.dtype`` before the rotary, the result to ``x.dtype``. In
    f32, where a product is not exact, to the contraction the CPU's
    compiler chose on either side: a last digit of the larger product."""
    (y, _, _), (ref, _, _), _ = _rotated(variant, case)
    assert y.dtype == ref.dtype and y.shape == ref.shape
    if y.dtype == jnp.bfloat16:
        assert np.array_equal(y, ref)
    else:
        assert rel_l2(y, ref) < 1e-7


@pytest.mark.parametrize("variant, case", ROTATED)
def test_dx_is_the_plain_gradient_and_no_further_than_two_passes(variant,
                                                                  case):
    """One rounding, at ``dx``, where the two passes round the rotary's
    cotangent to ``x.dtype`` on the way (three of them and their sum in
    the program: PERF.md section 6, PR 42)."""
    (_, dx, _), (_, two, _), (_, ref, _) = _rotated(variant, case)
    assert dx.dtype == two.dtype == jnp.dtype(ROTARY_CASES[case][3])
    f32 = ROTARY_CASES[case][3] == "float32"
    assert rel_l2(dx, ref) < (1e-6 if f32 else 1e-4)
    # in f32 both are at its rounding
    assert rel_l2(dx, ref) <= rel_l2(two, ref) + 1e-7 * f32


@pytest.mark.parametrize("case", ROTARY_CASES)
def test_dscale_through_the_rotary(case):
    (_, _, ds), (_, _, two), (_, _, ref) = _rotated("norm_rotary", case)
    assert ds.shape == ref.shape == (ROTARY_CASES[case][1],)
    assert ds.dtype == jnp.float32
    assert rel_l2(ds, ref) < 5e-6
    assert rel_l2(ds, ref) <= rel_l2(two, ref) + 1e-7


def test_the_rotary_alone_saves_no_array_of_the_inputs_size():
    """Its transpose needs the tables and the cotangent: residuals of size
    (B, T, H * d) are the norm's ``x`` alone."""
    x = jnp.ones((1, 64, 512), jnp.bfloat16)
    scale = jnp.ones((128,))

    tables = K.rotary_tables(*attention.rotary_cos_sin(jnp.arange(64), 128))

    def residuals(scale):
        _, vjp = jax.vjp(
            lambda x: K.per_head(x, scale, tables, EPS, 128, True), x)
        return [r.shape for r in jax.tree.leaves(vjp)
                if getattr(r, "shape", ()) == x.shape]

    assert residuals(None) == [] and residuals(scale) == [x.shape]


# ---------------------------------------------------------------------------
# The rotary of interleaved pairs
# ---------------------------------------------------------------------------

PAIR_THETA, PAIR_HEAD = 3.2e7, 64           # joyaiflash's

# (samples, rows, lanes of the array), the lane the rotated columns start
# at, dtype: the cell's two arrays at fewer rows (q_b's 6 144 lanes of
# which the last 2 048 turn, kv_a's 576 of which the last 64 are the key:
# half of a lane tile that ends past the array), each over two row tiles,
# small ones in f32, and arrays that are rotated whole
PAIR_CASES = {
    "q_2048_of_6144_two_samples_bf16": ((2, 1024, 6144), 4096, "bfloat16"),
    "k_64_of_576_two_tiles_bf16": ((1, 16384, 576), 512, "bfloat16"),
    "q_256_of_768_f32": ((1, 64, 768), 512, "float32"),
    "k_64_of_192_two_samples_f32": ((2, 104, 192), 128, "float32"),
    "k_64_the_whole_array_bf16": ((2, 512, 64), 0, "bfloat16"),
    "q_128_the_whole_array_bf16": ((2, 512, 128), 0, "bfloat16"),
}


@functools.lru_cache(maxsize=None)
def _paired(case):
    """(out, dx) of the one pass, of the expression it replaces on the
    slice (tables as wide as the rotated columns), and of that expression
    in f32 from the same input (``dx`` rounded to the input's dtype)."""
    shape, start, dtype = PAIR_CASES[case]
    width = shape[2] - start
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 2)
    x = (jax.random.normal(keys[0], shape) * 2.0 + 0.3).astype(dtype)
    w = jax.random.normal(keys[1], (*shape[:2], width)).astype(dtype)

    # both sides' tables from one compiled cosine, of 8 significant bits
    # beside a bf16 input's 8 (the reasons are _rotated's)
    def tables(lanes):
        angles = sparse_lm._pair_angles(shape[1], lanes, PAIR_HEAD,
                                        PAIR_THETA)
        ts = jnp.cos(angles), jnp.sin(angles)
        if dtype == "bfloat16":
            ts = tuple(t.astype(dtype).astype(jnp.float32) for t in ts)
        return ts
    wide, one = tables(width), tables(K.LANES)
    # one lane tile's table is every tile's, and a narrower array's
    assert all(np.array_equal(jnp.tile(a, (1, -(-width // K.LANES)))
                              [:, :width], b) for a, b in zip(one, wide))
    assert start % K.pair_block(width) == 0

    def one_pass(x):
        return K.pair_rotary(x, K.pair_tables(*one), start, True)

    def today(x):
        return sparse_lm.turn_pairs_lanes(x[..., start:], *wide)

    def outputs(fn, x, w):
        def run(x, w):
            y, vjp = jax.vjp(fn, x)
            return y, vjp(w)[0]
        return as_stated(run, x, w)

    return (outputs(one_pass, x, w), outputs(today, x, w),
            outputs(lambda x: today(x.astype(jnp.float32)), x,
                    w.astype(jnp.float32)))


@pytest.mark.parametrize("case", PAIR_CASES)
def test_the_pair_pass_forward_is_todays_expression_bit_for_bit(case):
    """The same f32 arithmetic a lane and one rounding, to ``x.dtype``; the
    sign rides in the sine where today's negates the shifted copy. In f32,
    where a product is not exact, to the contraction the CPU's compiler
    chose on either side."""
    (y, _), (ref, _), _ = _paired(case)
    shape, start, dtype = PAIR_CASES[case]
    assert y.dtype == ref.dtype == jnp.dtype(dtype)
    assert y.shape == ref.shape == (*shape[:2], shape[2] - start)
    if dtype == "bfloat16":
        assert np.array_equal(y, ref)
    else:
        assert rel_l2(y, ref) < 1e-7


@pytest.mark.parametrize("case", PAIR_CASES)
def test_the_pair_pass_dx_is_the_plain_gradient_and_no_further_than_todays(
        case):
    """One ``dx`` of the whole array's shape, nought in the columns the
    pass does not turn; f32 from ``dout`` to it, one rounding."""
    (_, dx), (_, xla), (_, ref) = _paired(case)
    shape, start, dtype = PAIR_CASES[case]
    assert dx.shape == shape and dx.dtype == xla.dtype == jnp.dtype(dtype)
    assert not np.any(np.asarray(dx[..., :start], np.float32))
    f32 = dtype == "float32"
    assert rel_l2(dx, ref) < (1e-6 if f32 else 1e-4)
    assert rel_l2(dx, ref) <= rel_l2(xla, ref) + 1e-7 * f32


# ... and one whose lanes before the rotated ones are no whole block of the
# pass: sliced first, then the pass
DISPATCHED = {**PAIR_CASES,
              "q_128_after_64_sliced_first_f32": ((1, 64, 192), 64,
                                                  "float32")}


@pytest.mark.parametrize("case", DISPATCHED)
def test_the_dispatcher_takes_the_pair_pass_with_its_own_tables(
        case, monkeypatch, lowering_record):
    """``pair_rotary`` from the array and the lane its rotary part starts
    at: the tables it makes for one lane tile, the block it names and the
    record ``attn_layout`` reads."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    shape, start, dtype = DISPATCHED[case]
    width = shape[2] - start
    x = jax.random.normal(jax.random.PRNGKey(5), shape).astype(dtype)
    y = as_stated(functools.partial(
        sparse_lm.pair_rotary, start=start, mesh=None, spec=LANES_SPEC,
        head_dim=PAIR_HEAD, theta=PAIR_THETA), x)
    ref = as_stated(lambda x: sparse_lm.rotary_interleaved_lanes(
        x[..., start:], PAIR_HEAD, PAIR_THETA), x)
    assert lowering_record.why_not("rotary", sparse_lm._pair_key(
        shape[1], width, PAIR_HEAD)) is None
    assert y.dtype == ref.dtype and y.shape == ref.shape
    # each side's cosine compiled into its own fusion: a last digit apart
    # at large angles, so a result may round to the other neighbour
    assert rel_l2(y, ref) < (1e-6 if dtype == "float32" else 1e-4)


@pytest.mark.parametrize("tokens, width, head_dim, interpret, why", [
    (8192, 2048, 64, True, None),           # the cell's two
    (8192, 64, 64, True, None),
    (64, 256, 128, True, None),             # one head a lane tile
    (64, 128, 32, True, None),              # four
    (64, 192, 64, True, "192 lanes are neither whole 128-lane tiles nor one "
                        "head of 64"),
    (64, 32, 16, True, "32 lanes are neither whole 128-lane tiles nor one "
                       "head of 16"),
    (64, 384, 48, True, "heads of 48 lanes are not whole heads of pairs a "
                        "128-lane tile"),
    (64, 256, 256, True, "heads of 256 lanes are not whole heads of pairs a "
                         "128-lane tile"),
    (60, 256, 64, True, "60 rows are not whole sublane tiles of 8"),
    (64, 1 << 18, 64, True, "8 rows of 262144 lanes pass a tile of 1048576 "
                            "numbers"),
    (8192, 2048, 64, False, "no Mosaic backend"),
])
def test_pair_rotary_why_not(tokens, width, head_dim, interpret, why,
                             monkeypatch, lowering_record):
    """The rule's answer on the local shapes; with no Mosaic backend the
    record answers for it, whatever was traced."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    assert lowering_record.why_not("rotary", sparse_lm._pair_key(
        tokens, width, head_dim)) == ("none traced" if interpret else why)
    if interpret:
        assert K.pairs_fit(tokens, width, head_dim) == why


@pytest.mark.parametrize("shape, start, head_dim, interpret", [
    ((2, 32, 576), 384, 64, True),          # 192 lanes: a tile and a half
    ((2, 32, 704), 512, 64, True),          # ... and so is 192 after 512
    ((1, 12, 384), 256, 64, True),          # 12 rows
    ((2, 32, 96), 0, 48, True),             # heads of 48
    ((2, 32, 384), 256, 64, False),         # no Mosaic backend
])
def test_what_the_pair_rule_refuses_is_todays_expression_bit_for_bit(
        shape, start, head_dim, interpret, monkeypatch, lowering_record):
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(keys[0], shape).astype(jnp.bfloat16)
    width = shape[2] - start
    w = jax.random.normal(keys[1], (*shape[:2], width)).astype(jnp.bfloat16)

    def today(x):
        return sparse_lm.rotary_interleaved_lanes(x[..., start:], head_dim,
                                                  PAIR_THETA)

    def now(x):
        return sparse_lm.pair_rotary(
            x, start, mesh=None, spec=LANES_SPEC, head_dim=head_dim,
            theta=PAIR_THETA)

    for fn, ref in ((now, today),
                    (jax.grad(lambda x: jnp.sum(now(x) * w)),
                     jax.grad(lambda x: jnp.sum(today(x) * w)))):
        a, b = jax.jit(fn)(x), jax.jit(ref)(x)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert lowering_record.why_not("rotary", sparse_lm._pair_key(
        shape[1], width, head_dim)) not in (None, "none traced")


@pytest.mark.parametrize("part", ["queries", "key"])
@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_the_pair_pass_per_shard_whole_pairs_of_heads(nested, part,
                                                      monkeypatch,
                                                      inside_manual_dp,
                                                      lowering_record):
    """dp 2 x fsdp 2 x tp 2: a shard holds a sample's rows of one pair of
    the four heads' rotary lanes (one lane tile: its tables are every
    tile's), and the one key whole."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    lanes, spec = ((256, LANES_SPEC) if part == "queries"
                   else (64, sparse_lm.ROPE_KEY_SPEC))
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(keys[0], (4, 24, lanes)) * 2.0
    w = jax.random.normal(keys[1], x.shape)

    def value_and_grads(mesh_):
        def f(x, w):
            out = sparse_lm.pair_rotary(x, 0, mesh=mesh_, spec=spec,
                                        head_dim=PAIR_HEAD, theta=PAIR_THETA)
            return jnp.sum(out * w), out
        vg = jax.value_and_grad(f, argnums=(0,), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (True, True), (0,))
        return jax.jit(vg)

    (_, out_m), g_m = value_and_grads(mesh)(x, w)
    # a tp shard's pair of the four heads, or the one key whole
    assert lowering_record.why_not("rotary", sparse_lm._pair_key(
        24, lanes, PAIR_HEAD, 2 if part == "queries" else 1)) is None
    (_, out_1), g_1 = value_and_grads(None)(x, w)
    assert len(out_m.sharding.device_set) == 8
    np.testing.assert_allclose(out_m, out_1, rtol=1e-6, atol=1e-6)
    assert rel_l2(g_m[0], g_1[0]) < 1e-6
    ref = sparse_lm.rotary_interleaved_lanes(x, PAIR_HEAD, PAIR_THETA)
    np.testing.assert_allclose(out_m, ref, rtol=1e-5, atol=1e-5)


def test_the_pair_pass_saves_no_array_of_the_inputs_size():
    """Its transpose needs the tables and the cotangent: no residual has
    the shape of ``q_b``'s output, nor of the columns turned."""
    x = jnp.ones((1, 64, 768), jnp.bfloat16)
    angles = sparse_lm._pair_angles(64, K.LANES, PAIR_HEAD, PAIR_THETA)
    tables = K.pair_tables(jnp.cos(angles), jnp.sin(angles))
    out, vjp = jax.vjp(lambda x: K.pair_rotary(x, tables, 512, True), x)
    assert out.shape == (1, 64, 256)
    kept = [r.shape for r in jax.tree.leaves(vjp) if hasattr(r, "shape")]
    assert kept and x.shape not in kept and out.shape not in kept
    assert set(kept) <= {(64, K.LANES)}
