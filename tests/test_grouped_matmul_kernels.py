"""The grouped products of an expert layer (ops/pallas/grouped_matmul_kernels.py),
interpreted on the CPU, each against plain ``jax.numpy`` on the rows its
plan says hold an assignment: the three products a direction, and the
expert block's three kernels that do a tile's activation, cotangents and
sum of the two ``dxs`` while the tile is in VMEM. Plans with an empty
expert, one expert holding every row, a buffer exactly full and a buffer
with one active tile; ReLU and SiLU; bf16 and f32. No row of an inactive
tile is read (poisoned with NaN, the active rows' results stay to the last
bit) and none is written (interpreted, an unwritten row reads NaN: the
callers mask them)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.pallas import grouped_matmul_kernels as K

TILE = 32
D, F = 64, 128

# name: (rows an expert, row tiles of the buffer)
PLANS = {
    "an_empty_expert": ((70, 0, 33, 5), 10),
    "one_expert_holds_every_row": ((0, 0, 150, 0), 12),
    "a_buffer_exactly_full": ((64, 32, 1, 95), 7),
    "one_active_tile": ((9,), 4),
}


def plan(name):
    """(Tiles, (rows,) the row's expert, (rows,) the row is in an active
    tile, (rows,) the row holds an assignment)."""
    sizes, n_tiles = PLANS[name]
    first, tiles = K.tile_plan(jnp.asarray(sizes, jnp.int32), n_tiles, TILE)
    expert = np.repeat(np.asarray(tiles.expert), TILE)
    active = np.repeat(np.asarray(tiles.active), TILE) == 1
    within = np.arange(n_tiles * TILE) - np.asarray(first)[expert]
    return tiles, expert, active, active & (within < np.asarray(sizes)[expert])


def test_the_plans_are_what_their_names_say():
    active = lambda name: np.asarray(plan(name)[0].active)
    assert active("an_empty_expert").tolist() == [1] * 7 + [0] * 3
    assert active("one_expert_holds_every_row").tolist() == [1] * 8 + [0] * 4
    assert active("a_buffer_exactly_full").all()
    assert active("one_active_tile").tolist() == [1, 0, 0, 0]
    # a step past the last active tile names that tile's block: no DMA
    for name in PLANS:
        tiles = plan(name)[0]
        last = int(np.sum(active(name))) - 1
        np.testing.assert_array_equal(
            tiles.block, np.minimum(np.arange(len(active(name))), last))
        assert tiles.expert[last] == tiles.expert[-1]


def operands(name, dtype, seed=0):
    tiles, expert, active, valid = plan(name)
    rows, experts = len(expert), len(PLANS[name][0])
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda key, *shape: jax.random.normal(key, shape)
    # rows of an active tile that hold no assignment are zero, as
    # ``_to_rows`` leaves them
    on_rows = lambda a: jnp.where(valid[:, None], a, 0).astype(dtype)
    return dict(
        x=on_rows(normal(keys[0], rows, D)),
        dy=on_rows(normal(keys[1], rows, D)),
        g=on_rows(normal(keys[2], rows, F)),
        u=on_rows(normal(keys[3], rows, F)),
        dhid=on_rows(normal(keys[4], rows, F)),
        gate=(normal(keys[5], experts, D, F) * 0.2).astype(dtype),
        up=(normal(keys[6], experts, D, F) * 0.2).astype(dtype),
        down=(normal(keys[7], experts, F, D) * 0.2).astype(dtype))


def by_row(x, w, expert, transpose_w=False):
    """Every row times its own expert's weights, f32."""
    w = jnp.swapaxes(w, 1, 2) if transpose_w else w
    return jnp.einsum("rk,rkn->rn", x.astype(jnp.float32),
                      w.astype(jnp.float32)[expert],
                      precision=jax.lax.Precision.HIGHEST)


def by_expert(x, dy, expert, experts, valid):
    """(experts, K, N) f32: ``x.T @ dy`` over every expert's rows."""
    one_hot = (expert[:, None] == np.arange(experts)) & valid[:, None]
    return jnp.einsum("re,rk,rn->ekn", one_hot.astype(jnp.float32),
                      x.astype(jnp.float32), dy.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


# every kernel as (what it reads by rows, f(operands, tiles, act) -> outputs,
# the same in plain jax.numpy from f32 products rounded where the kernel
# rounds)
def _gated_hidden_reference(o, expert, valid, act):
    g = by_row(o["x"], o["gate"], expert).astype(o["x"].dtype)
    u = by_row(o["x"], o["up"], expert).astype(o["x"].dtype)
    return g, u, K.act(act, g) * u


def _gated_hidden_grads_reference(o, expert, valid, act):
    dhidden = by_row(o["dy"], o["down"], expert, True).astype(o["g"].dtype)
    hidden = K.act(act, o["g"])
    return (K.gate_cotangent(act, o["g"], o["u"], dhidden), dhidden * hidden,
            hidden * o["u"])


KERNELS = {
    "grouped_matmul": (
        ("x",),
        lambda o, tiles, act: (K.grouped_matmul(
            o["x"], o["gate"], tiles, TILE, True),),
        lambda o, expert, valid, act: (
            by_row(o["x"], o["gate"], expert).astype(o["x"].dtype),)),
    "rows_gradient": (
        ("x", "dhid"),
        lambda o, tiles, act: (K.grouped_matmul_grads(
            o["x"], o["gate"], o["dhid"], tiles, TILE, True)[0],),
        lambda o, expert, valid, act: (
            by_row(o["dhid"], o["gate"], expert, True).astype(o["x"].dtype),
        )),
    "weights_grad": (
        ("x", "dhid"),
        lambda o, tiles, act: (K.weights_grad(
            o["x"], o["dhid"], tiles, o["gate"], TILE, True),),
        lambda o, expert, valid, act: (
            by_expert(o["x"], o["dhid"], expert, o["gate"].shape[0],
                      valid).astype(o["gate"].dtype),)),
    "gated_hidden": (
        ("x",),
        lambda o, tiles, act: K.gated_hidden(
            o["x"], o["gate"], o["up"], tiles, act, TILE, True),
        _gated_hidden_reference),
    "gated_hidden_grads": (
        ("dy", "g", "u"),
        lambda o, tiles, act: K.gated_hidden_grads(
            o["dy"], o["down"], o["g"], o["u"], tiles, act, TILE, True),
        _gated_hidden_grads_reference),
    "rows_grad": (
        ("g", "u"),
        lambda o, tiles, act: (K.rows_grad(
            o["g"], o["u"], o["gate"], o["up"], tiles, TILE, True),),
        lambda o, expert, valid, act: (
            (by_row(o["g"], o["gate"], expert, True)
             + by_row(o["u"], o["up"], expert, True)).astype(o["g"].dtype),)),
}


# a product has no activation: its one case is named "relu"
WITH_ACTIVATIONS = [(kernel, act) for kernel in KERNELS
                    for act in ("relu", "silu")
                    if act == "relu" or "gated" in kernel]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(PLANS))
@pytest.mark.parametrize("kernel, act", WITH_ACTIVATIONS)
def test_a_kernel_against_plain_numpy_on_the_rows_that_hold_an_assignment(
        kernel, act, name, dtype):
    reads, run, reference = KERNELS[kernel]
    tiles, expert, active, valid = plan(name)
    o = operands(name, dtype)
    got = jax.jit(lambda o: run(o, tiles, act))(o)
    want = reference(o, expert, valid, act)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == "bfloat16" \
        else dict(rtol=1e-5, atol=1e-5)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    by_rows = kernel != "weights_grad"
    # what a caller reads: the rows of active tiles, or every expert's block
    read = lambda a: f32(a)[active] if by_rows else f32(a)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(read(a)).all() and np.abs(read(b)).max() > 0
        np.testing.assert_allclose(read(a), read(b), **tol)
    # no row of an inactive tile is read: poisoned, the results stand
    poisoned = dict(o, **{k: jnp.where(active[:, None], o[k], jnp.nan)
                          for k in reads})
    again = jax.jit(lambda o: run(o, tiles, act))(poisoned)
    for a, b in zip(got, again):
        np.testing.assert_array_equal(read(a), read(b))
    # and none is written: what the interpreter's unwritten rows hold (NaN)
    # is still there, for the callers to mask
    if by_rows and not active.all():
        for a in got:
            assert np.isnan(f32(a)[~active]).all()


def test_which_experts_the_block_fits_is_read_off_their_sizes():
    """Two weight blocks with two buffers each, the tiles and the f32
    intermediates within the kernels' VMEM limit: the four cells' experts
    fit, a width whose two blocks alone pass the limit does not."""
    why_not = K.block_why_not
    for dim, width in ((2560, 768), (2048, 1024), (2048, 768), (2048, 1792)):
        assert why_not(dim, width, "bfloat16") is None
    assert why_not(2048, 1792, "float32") == (
        "two blocks of 2048 x 1792 and the tiles need 75.8 MiB of VMEM, "
        "over 64")
    assert "two blocks of 2048 x 4096" in why_not(2048, 4096, "bfloat16")
    assert why_not(64, 32, "float32") is None


@pytest.mark.parametrize("name", list(PLANS))
def test_one_dxs_is_the_two_products_summed_in_f32_and_rounded_once(name):
    """The one difference between the expert block and the three products
    a direction: ``rows_grad`` in a 16-bit dtype is the f32 sum of the two
    unrounded products rounded once, where two rounded buffers were added
    and rounded again; in f32 it is their sum to the last bit."""
    tiles, expert, active, valid = plan(name)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    for dtype in ("bfloat16", "float32"):
        o = operands(name, dtype)
        one = K.rows_grad(o["g"], o["u"], o["gate"], o["up"], tiles, TILE,
                          True)
        # the same numbers widened: the products come out unrounded
        wide = {k: v.astype(jnp.float32) for k, v in o.items()}
        by_gate, by_up = (
            K.grouped_matmul_grads(wide["x"], wide[w], wide[dy], tiles,
                                   TILE, True)[0]
            for w, dy in (("gate", "g"), ("up", "u")))
        once = (by_gate + by_up).astype(dtype)
        twice = by_gate.astype(dtype) + by_up.astype(dtype)
        differ = lambda a: float(np.mean(f32(one)[active] != f32(a)[active]))
        if dtype == "float32":
            assert differ(once) == 0.0 and differ(twice) == 0.0
        else:
            # (the widened operands' products may sum in another order: a
            # near-tie in a few thousand rounds the other way)
            assert differ(once) < 1e-3 and differ(twice) > 0.05
