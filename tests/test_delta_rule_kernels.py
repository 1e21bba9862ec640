"""The gated delta rule's kernel pair (ops/pallas/delta_rule_kernels.py),
interpreted on the CPU, against the XLA lowering of the same site
(``sparse_lm.chunked_delta_rule``) and the yardstick's token-by-token
recurrence: ``o`` and every operand's gradient through a rematerialised
call (the forward, its replay and the backward), one grid step and several,
one value head a key head and two, one key head a grid step and two, keys
that resemble each other (the inverse's hard case), a state remembered
across chunks (``A`` = 0.05) and one forgotten within a token (``A`` as the
source draws it), keys wider than values, chunks of 16, two samples, f32 and
bfloat16; the site's predicate, each refusal with its recorded
reason and the XLA lowering's result; per shard on the 8-device mesh; a
rematerialised layer of the model keeps what the rule made (one forward
kernel in its gradient's program, two without the rule's names in the
policy) and computes the same numbers either way."""
import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest
from dalle_tpu.config import Qwen3NextLMConfig
from dalle_tpu.models import attention, sparse_lm
from dalle_tpu.ops.pallas import delta_rule_kernels as K
from dalle_tpu.parallel.mesh import make_mesh
import sparse_family as fam
from sparse_family import rel_l2

Y = Manifest().yardstick("qwen3next")
OPERANDS = ("q", "k", "v", "g", "beta")

# samples, tokens, key heads, value heads, dk, dv, chunk; then how the keys
# and ``A`` are drawn
SHAPES = {
    "one_step_one_head_a_key_head": ((1, 128, 1, 1, 128, 128, 64), {}),
    "two_steps_two_heads_a_key_head": ((2, 256, 1, 2, 128, 128, 64), {}),
    "keys_that_resemble_each_other": ((1, 128, 2, 4, 128, 128, 64),
                                      dict(alike=True)),
    "a_state_forgotten_within_a_token_keys_of_two_tiles": (
        (1, 128, 1, 2, 256, 128, 64), dict(a=(0.0, 16.0))),
    "chunks_of_16": ((1, 64, 1, 2, 128, 128, 16), {}),
}
# tokens a grid step where not the kernels' own: the state, and its
# cotangent, carried from one grid step to the next
STEP_TOKENS = {"two_steps_two_heads_a_key_head": 128, "chunks_of_16": 32}


def _operands(shape, dtype, alike=False, a=(0.05, 0.05), seed=0):
    """q and k normalised as the mixer hands them over; ``A`` = 0.05 keeps
    a state some fifteen tokens (drawn as the source draws it, from U(0,
    16), 31 of 32 heads forget within a token: PERF.md section 6, PR 64);
    ``alike``: every key near one direction, so that ``A``'s entries are
    near ``beta`` and the inverse's entries cancel."""
    b, t, g, h, dk, dv, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 8)
    k = jax.random.normal(keys[1], (b, t, g * dk))
    if alike:
        k = 0.05 * k + jax.random.normal(keys[6], (b, 1, g * dk))
    decay = jax.random.uniform(keys[7], (h,), minval=a[0], maxval=a[1])
    return (sparse_lm.l2_normed(jax.random.normal(keys[0], (b, t, g * dk)),
                                dk, dk ** -0.5).astype(dtype),
            sparse_lm.l2_normed(k, dk).astype(dtype),
            jax.random.normal(keys[2], (b, t, h * dv)).astype(dtype),
            -decay * jax.nn.softplus(jax.random.normal(keys[3], (b, t, h))),
            jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h))),
            jax.random.normal(keys[5], (b, t, h * dv)))


def _value_and_grads(rule, operands):
    """``o`` and the gradient of every operand through a rematerialised
    call of ``rule``."""
    *operands, weigh = operands

    def loss(*o):
        y = jax.checkpoint(rule)(*o)
        return jnp.sum(y.astype(jnp.float32) * weigh), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(OPERANDS))), has_aux=True))(*operands)
    return (y,) + grads


def _by_token(shape):
    _, _, g, h, dk, dv, _ = shape

    def rule(q, k, v, log_decay, beta):
        b, t = v.shape[:2]
        return Y.delta_recurrence(
            q.reshape(b, t, g, dk), k.reshape(b, t, g, dk),
            v.reshape(b, t, g, h // g, dv), log_decay.reshape(b, t, g, -1),
            beta.reshape(b, t, g, -1)).reshape(v.shape)
    return rule


@functools.lru_cache(maxsize=None)
def _three(name, dtype):
    """(kernels, XLA lowering, recurrence in f32), each ``o`` and the five
    gradients."""
    shape, drawn = SHAPES[name]
    operands = _operands(shape, dtype, **drawn)
    sizes = dict(key_heads=shape[2], chunk=shape[6])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(K, "STEP_TOKENS", STEP_TOKENS.get(name, K.STEP_TOKENS))
        kernels = _value_and_grads(
            functools.partial(K.rule, **sizes, interpret=True), operands)
    xla = _value_and_grads(
        functools.partial(sparse_lm.chunked_delta_rule, **sizes), operands)
    exact = tuple(o.astype(jnp.float32) for o in operands)
    return kernels, xla, _value_and_grads(_by_token(shape), exact)


@pytest.mark.parametrize("name", list(SHAPES))
def test_the_kernels_are_the_chunked_rule_and_the_recurrence_f32(name):
    """f32: the XLA lowering's numbers, and the recurrence's as near as the
    XLA lowering's own lie (where keys resemble each other the chunk's
    system is ill-conditioned for both: tests/test_qwen3next_model.py)."""
    assert K.fits(*SHAPES[name][0][1:], 4) is None
    kernels, xla, by_token = _three(name, "float32")
    for what, got, want, true in zip(("o",) + OPERANDS, kernels, xla,
                                     by_token):
        assert got.shape == want.shape and got.dtype == want.dtype, what
        assert rel_l2(got, want) < 1e-5, what
        assert rel_l2(got, true) < max(1.5 * rel_l2(want, true), 2e-5), what


@pytest.mark.parametrize("name", list(SHAPES)[1:4])
def test_in_bfloat16_the_kernels_lie_as_near_the_recurrence_as_the_xla_code(
        name):
    """bfloat16 operands: each result no further from the f32 recurrence
    on the same numbers than the XLA lowering's own distance (half as far
    again, or a bfloat16 step of 2^-8 where that is less)."""
    kernels, xla, by_token = _three(name, "bfloat16")
    for what, got, want, true in zip(("o",) + OPERANDS, kernels, xla,
                                     by_token):
        assert got.dtype == want.dtype, what
        assert rel_l2(got, true) < max(1.5 * rel_l2(want, true), 2 ** -8), (
            what, rel_l2(got, true), rel_l2(want, true))


def test_the_inverse_in_the_kernel_is_the_xla_lowerings():
    """``_unit_lower_inverses`` on whole (64 x 64) operands with masks
    against ``sparse_lm.unit_lower_inverse`` and a solve in f64, on the
    matrices of keys that all point one way."""
    c = 64
    rng = np.random.default_rng(0)
    k = rng.normal(size=(c, 8)) * 0.05 + 1.0
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    a = np.tril(k @ k.T, -1)
    want = np.linalg.inv(np.eye(c) + a)
    for whole, near in ((True, 1e-5), (False, 1e-4)):
        got, = jax.jit(lambda a: K._unit_lower_inverses([a], c, whole))(
            jnp.asarray(a, jnp.float32))
        assert rel_l2(got, want) < near, whole
    # two heads' matrices as one block-diagonal operand
    both = np.zeros((2 * c, 2 * c))
    both[:c, :c], both[c:, c:] = a, a.T[::-1, ::-1]
    got, = K._unit_lower_inverses([jnp.asarray(both, jnp.float32)], c, True)
    assert rel_l2(got, np.linalg.inv(np.eye(2 * c) + both)) < 1e-5
    assert rel_l2(got[:c, :c], sparse_lm.unit_lower_inverse(
        jnp.asarray(a, jnp.float32))) < 1e-6
    assert float(jnp.abs(got[:c, c:]).max()) == 0


def test_a_steps_chunks_keys_and_packs_divide_the_sample_and_fit_vmem(
        monkeypatch):
    sizes = (2, 128, 128, 2)         # heads a key head, dk, dv, bf16
    # two heads of 64 tokens are one (128 x 128) operand; of 128, two
    assert [K.pack_of(r, q) for r, q in
            ((2, 64), (2, 128), (4, 64), (3, 32), (1, 64), (8, 16))] == [
                2, 1, 2, 3, 1, 8]
    # the cell's: 8 of 16 key heads and 4 chunks a step
    assert K.keys_a_step(16, 2, 64) == 8 and K.keys_a_step(6, 2, 64) == 6
    assert K.keys_a_step(7, 2, 64) == 7 and K.keys_a_step(11, 2, 64) == 1
    assert K.chunks_a_step(128, 64, *sizes, 8) == 4
    assert K.chunks_a_step(128, 64, *sizes, 1) == 16
    assert K.chunks_a_step(3, 64, *sizes) == 3
    assert K.chunks_a_step(34, 64, *sizes) == 2
    assert K.vmem_bytes(64, *sizes, 16) < K.vmem_bytes(64, *sizes, 4, 8) \
        < K._VMEM < K.vmem_bytes(64, *sizes, 8, 8)
    assert K._groups(16, 8) == (16, 1) and K._groups(16, 2) == (4, 4)
    assert K._groups(6, 2) == (2, 3) and K._groups(7, 1) == (1, 7)
    monkeypatch.setattr(K, "STEP_TOKENS", 128)
    assert K.chunks_a_step(4, 64, *sizes) == 2      # two grid steps of two
    assert K.chunks_a_step(7, 512, *sizes) == 1
    monkeypatch.setattr(K, "STEP_TOKENS", 1 << 16)
    assert K.vmem_bytes(64, *sizes, K.chunks_a_step(1024, 64, *sizes)) \
        <= K._VMEM < K.vmem_bytes(64, *sizes, 1024)


TINY = dict(hidden_size=64, num_hidden_layers=1,
            layer_kinds=("gated_delta",), num_heads=4, num_kv_heads=2,
            head_dim=16, vocab_size=96, text_seq_len=27, image_grid=4,
            vocab_text=48, vocab_image=48, dtype="float32")
FITS = dict(linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128,
            delta_chunk=64)
SMALL = dict(linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8, delta_chunk=16)


@pytest.mark.parametrize("tokens, sizes, why", [
    (128, {}, None),
    (100, {}, "100 tokens are not whole chunks of 64"),
    (128, dict(linear_key_head_dim=64),
     "heads of 64 and 128 lanes are not whole 128-lane tiles"),
    (128, dict(linear_value_head_dim=192),
     "heads of 128 and 192 lanes are not whole 128-lane tiles"),
    (128, dict(delta_chunk=4),
     "a chunk of 4 is no power of two of at least a sublane tile of 8"),
    # the tests' tiny model
    (43, SMALL, "43 tokens are not whole chunks of 16"),
    (48, SMALL, "heads of 8 and 8 lanes are not whole 128-lane tiles"),
])
def test_the_site_takes_the_kernels_or_says_why_not(tokens, sizes, why,
                                                    monkeypatch,
                                                    lowering_record):
    """The predicate on the local shapes, through the site: the record
    holds the reason (or the kernels' facts), and a refused call is the XLA
    lowering's result bit for bit."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = Qwen3NextLMConfig(**dict(TINY, **dict(FITS, **sizes)))
    g, h = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    chunk = cfg.delta_chunk
    *operands, _ = _operands((1, tokens, g, h, dk, dv, chunk), "float32")
    got = jax.jit(functools.partial(sparse_lm.delta_rule, mesh=None,
                                    cfg=cfg))(*operands)
    want = jax.jit(functools.partial(sparse_lm.chunked_delta_rule,
                                     key_heads=g, chunk=chunk))(*operands)
    key = sparse_lm._delta_key(tokens, cfg)
    assert key == (tokens, g, h, dk, dv, chunk)
    said = lowering_record.recorded(sparse_lm.DELTA_SITE, key)
    assert lowering_record.why_not(sparse_lm.DELTA_SITE, key) == why
    if why is None:
        assert said == {"why_not": None, "chunks_a_step": 2,
                        "keys_a_step": 1, "backward": K.BACKWARD}
        assert rel_l2(got, want) < 1e-5
    else:
        assert said == {"why_not": why}
        np.testing.assert_array_equal(got, want)


def test_heads_rows_and_blocks_the_kernels_cannot_hold_are_refused(
        monkeypatch):
    assert K.fits(8192, 16, 32, 128, 128, 64, 2) is None     # the cell's
    assert K.fits(8160, 16, 32, 128, 128, 48, 2) == (
        "a chunk of 48 is no power of two of at least a sublane tile of 8")
    assert K.fits(8192, 16, 40, 128, 128, 64, 2) == (
        "40 value heads are no whole multiple of 16 key heads")
    assert K.fits(8192, 1, 128, 128, 128, 64, 2) == (
        "128 value heads a key head pass 42 rows of a tile")
    assert K.fits(8192, 16, 32, 128, 128, 256, 2) == (
        "a chunk of 256 passes a lane tile of 128")
    monkeypatch.setattr(K, "_VMEM", 1 << 20)
    assert K.fits(8192, 16, 32, 128, 128, 64, 2).startswith(
        "a chunk of 64 x 256 and a state of 128 x 256 need ")


@pytest.mark.parametrize("nested", [False, True],
                         ids=["whole_mesh", "inside_manual_dp"])
def test_per_shard_a_shard_holds_samples_and_every_head(
        nested, monkeypatch, inside_manual_dp, lowering_record):
    """dp 2 x fsdp 2 x tp 2: a shard holds one of the four samples, every
    head of it (no axis splits the mixer's lanes)."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    mesh = make_mesh(dp=2, fsdp=2, tp=2)
    cfg = Qwen3NextLMConfig(**dict(TINY, **FITS))
    *operands, weigh = _operands((4, 128, 1, 2, 128, 128, 64), "float32")

    def value_and_grads(mesh_):
        def f(q, k, v, g, beta, w):
            y = sparse_lm.delta_rule(q, k, v, g, beta, mesh=mesh_, cfg=cfg,
                                     scope="rule")
            return jnp.sum(y * w), y
        vg = jax.value_and_grad(f, argnums=tuple(range(5)), has_aux=True)
        if nested and mesh_ is not None:
            vg = inside_manual_dp(vg, mesh_, (True,) * 6, tuple(range(5)))
        return jax.jit(vg)

    (_, y_m), g_m = value_and_grads(mesh)(*operands, weigh)
    (_, y_1), g_1 = value_and_grads(None)(*operands, weigh)
    assert len(y_m.sharding.device_set) == 8
    assert lowering_record.why_not(
        sparse_lm.DELTA_SITE, sparse_lm._delta_key(128, cfg)) is None
    np.testing.assert_allclose(y_m, y_1, rtol=1e-6, atol=1e-6)
    for name, got, want in zip(OPERANDS, g_m, g_1):
        assert rel_l2(got, want) < 1e-6, name


# one gated-delta layer of the model (mixer and expert block) at the widths
# the kernels take, 128 tokens in two fields
LAYER = dict(TINY, **FITS, text_seq_len=64, image_grid=8)
_OTHERS = tuple(name for name in sparse_lm.KEPT_OF_A_LAYER
                if name not in K.KEPT)
# what the policy of a rematerialised layer names, by case, and what the
# gradient's program then holds: forward kernels, and ``by_row`` transposes
# of the ``g`` and ``beta`` rows (one each in a forward pass)
POLICIES = {
    "without_the_rules_names": (_OTHERS, 2, 4),
    "what_the_kernel_made_alone": (_OTHERS + (K.KEPT_MADE,), 1, 4),
    "as_shipped": (sparse_lm.KEPT_OF_A_LAYER, 1, 2),
}
ROWS_PERMUTATION = (0, 3, 1, 4, 5, 2)            # ``K.rule``'s ``by_row``


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [
                    value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@functools.cache
def _layer(policy):
    """(the jaxpr of the model's gradient, its loss and gradients) with
    ``POLICIES[policy]``'s names as ``KEPT_OF_A_LAYER``: one program a
    policy."""
    cfg = Qwen3NextLMConfig(**LAYER)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_PALLAS_INTERPRET", True)
        patch.setattr(sparse_lm, "KEPT_OF_A_LAYER", POLICIES[policy][0])
        weights, operands = fam.params(cfg), fam.batch(cfg, n=1)
        step = fam.program(cfg)
        return (step.trace(weights, *operands).jaxpr,
                jax.device_get(step(weights, *operands)))


@pytest.mark.parametrize("policy", list(POLICIES))
def test_a_rematerialised_layer_runs_the_forward_kernel_once(policy):
    """The gradient of the model's one ``gated_delta`` layer under
    ``nn.remat``: with the rule's names in ``KEPT_OF_A_LAYER`` the replay
    reads ``o``, the states and the inverses the first forward wrote, and
    with the name of what the kernel read it makes no row of ``g`` and
    ``beta`` again; with the names taken out it runs the forward kernel a
    second time (so: the names reach the policy)."""
    assert K.fits(128, 1, 2, 128, 128, 64, 4) is None
    assert set(K.KEPT) <= set(sparse_lm.KEPT_OF_A_LAYER)
    _, forwards, rows = POLICIES[policy]
    equations = list(_equations(_layer(policy)[0]))
    calls = collections.Counter(
        eqn.params["jaxpr"].debug_info.func_name for eqn in equations
        if eqn.primitive.name == "pallas_call")
    assert calls["_delta_rule_fwd_kernel"] == forwards
    assert calls["_delta_rule_bwd_kernel"] == 1
    assert sum(eqn.primitive.name == "transpose"
               and tuple(eqn.params["permutation"]) == ROWS_PERMUTATION
               for eqn in equations) == rows


@pytest.mark.parametrize("policy", list(POLICIES)[1:])
def test_what_the_layer_keeps_changes_no_number(policy):
    """Loss, aux and every gradient leaf under a policy with the rule's
    names are those under the policy without them, bit for bit: the replay
    made the same values from the same operands with the same program."""
    (loss, aux), grads = _layer(policy)[1]
    (loss_, aux_), grads_ = _layer("without_the_rules_names")[1]
    assert loss == loss_
    theirs = fam.leaves((aux_, grads_))
    for name, got in fam.leaves((aux, grads)).items():
        np.testing.assert_array_equal(got, theirs[name], err_msg=name)


def test_what_the_names_keep_a_sample_and_layer():
    """``kept_bytes``: ``o``, the states a grid step, the inverses, ``q``,
    ``k`` and the two rows; 258 MiB at the cell's sizes."""
    assert K.kept_bytes(8192, 16, 32, 128, 128, 64, 2) == (
        64 + 64 + 64 + 32 + 32 + 1 + 1) << 20
    # one grid step of two chunks, one pack of two heads
    assert K.kept_bytes(128, 1, 2, 128, 128, 64, 4) == (
        128 * 256 * 4 + 128 * 256 * 4 + 128 * 2 * 64 * 4
        + 2 * 128 * 128 * 4 + 2 * 2 * 8 * 128 * 4)
