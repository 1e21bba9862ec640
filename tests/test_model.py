"""Core model tests: mask semantics, axial fast path vs dense oracle,
causality, weight sharing, loss behavior."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import (
    ATTN_AXIAL_COL,
    ATTN_AXIAL_ROW,
    ATTN_CONV_LIKE,
    ATTN_FULL,
    ModelConfig,
    tiny_model_config,
)
from dalle_tpu.models.attention import (
    axial_attention,
    dense_zoo_attention,
    zoo_attention_mask,
)
from dalle_tpu.models.dalle import DALLE, param_count


TEXT, GRID = 5, 4
IMG = GRID * GRID
T = TEXT + IMG


def _qkv(key, b=2, h=2, d=8, t=T):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, t, h, d), jnp.float32) for k in ks)


class TestMasks:
    def test_full_is_plain_causal(self):
        m = zoo_attention_mask(ATTN_FULL, TEXT, GRID)
        idx = np.arange(T)
        np.testing.assert_array_equal(m, idx[None, :] <= idx[:, None])

    def test_text_rows_causal_text_only(self):
        for at in (ATTN_AXIAL_ROW, ATTN_AXIAL_COL, ATTN_CONV_LIKE):
            m = zoo_attention_mask(at, TEXT, GRID)
            assert not m[:TEXT, TEXT:].any()  # text never sees image
            sub = m[:TEXT, :TEXT]
            idx = np.arange(TEXT)
            np.testing.assert_array_equal(sub, idx[None, :] <= idx[:, None])

    def test_image_sees_all_text(self):
        for at in (ATTN_FULL, ATTN_AXIAL_ROW, ATTN_AXIAL_COL, ATTN_CONV_LIKE):
            m = zoo_attention_mask(at, TEXT, GRID)
            assert m[TEXT:, :TEXT].all()

    def test_axial_row_pattern(self):
        m = zoo_attention_mask(ATTN_AXIAL_ROW, TEXT, GRID)
        # token (2, 3) attends to (2, 0..3) and nothing else in the image
        q = TEXT + 2 * GRID + 3
        ks = np.where(m[q, TEXT:])[0]
        np.testing.assert_array_equal(ks, 2 * GRID + np.arange(4))

    def test_axial_col_pattern(self):
        m = zoo_attention_mask(ATTN_AXIAL_COL, TEXT, GRID)
        q = TEXT + 2 * GRID + 3  # (r=2, c=3)
        ks = np.where(m[q, TEXT:])[0]
        np.testing.assert_array_equal(ks, np.array([0, 1, 2]) * GRID + 3)

    def test_conv_like_window_and_causal(self):
        m = zoo_attention_mask(ATTN_CONV_LIKE, TEXT, GRID, conv_kernel=3)
        q = TEXT + 2 * GRID + 2  # (2,2), window 3x3 => (1..3, 1..3) causal
        ks = set(np.where(m[q, TEXT:])[0])
        expect = set()
        for r in (1, 2, 3):
            for c in (1, 2, 3):
                if r * GRID + c <= 2 * GRID + 2:
                    expect.add(r * GRID + c)
        assert ks == expect

    def test_every_query_attends_to_something(self):
        for at in (ATTN_FULL, ATTN_AXIAL_ROW, ATTN_AXIAL_COL, ATTN_CONV_LIKE):
            m = zoo_attention_mask(at, TEXT, GRID)
            assert m.any(axis=1).all()
            assert np.diag(m).all()  # self-attention always allowed


class TestAxialFastPath:
    @pytest.mark.parametrize("at", [ATTN_AXIAL_ROW, ATTN_AXIAL_COL])
    def test_matches_dense_oracle(self, at):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        fast = axial_attention(q, k, v, at, TEXT, GRID)
        dense = dense_zoo_attention(q, k, v, at, TEXT, GRID)
        np.testing.assert_allclose(np.asarray(fast), np.asarray(dense),
                                   atol=1e-5, rtol=1e-5)


class TestCausality:
    """Perturbing future tokens must not change earlier predictions."""

    @pytest.mark.parametrize("at", [ATTN_FULL, ATTN_AXIAL_ROW,
                                    ATTN_AXIAL_COL, ATTN_CONV_LIKE])
    def test_future_image_token_does_not_leak(self, at):
        cfg = tiny_model_config(
            text_seq_len=TEXT, image_grid=GRID, depth=2,
            attn_types=(at,), conv_kernel=3)
        model = DALLE(cfg)
        rng = jax.random.PRNGKey(1)
        params = init_params(model, rng, batch=1)
        text = jax.random.randint(rng, (1, TEXT), 0, cfg.vocab_text)
        img = jax.random.randint(rng, (1, IMG), 0, cfg.vocab_image)

        @jax.jit
        def logits_fn(image_tokens, text=text):
            _, _, logits = model.apply(params, text, image_tokens,
                                       return_logits=True)
            return logits

        base = logits_fn(img)
        # Flip the LAST image token; logits at every earlier position must be
        # identical (position p's input only contains tokens < p).
        img2 = img.at[0, -1].set((img[0, -1] + 1) % cfg.vocab_image)
        pert = logits_fn(img2)
        np.testing.assert_allclose(np.asarray(base[:, :-1]),
                                   np.asarray(pert[:, :-1]),
                                   atol=1e-5, rtol=1e-5)
        # Flip the first text token; EVERY later position may change, and the
        # position predicting text token 0 must not (it only sees BOS).
        text2 = text.at[0, 0].set((text[0, 0] + 1) % cfg.vocab_text)
        pert_t = np.asarray(logits_fn(img, text2))
        np.testing.assert_allclose(np.asarray(base)[:, 0], pert_t[:, 0],
                                   atol=1e-5, rtol=1e-5)


class TestModel:
    def test_forward_shapes_and_finite(self):
        cfg = tiny_model_config()
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        text = jnp.zeros((2, cfg.text_seq_len), jnp.int32)
        img = jnp.zeros((2, cfg.image_seq_len), jnp.int32)
        loss, aux, logits = jax.jit(lambda *a: model.apply(
            *a, return_logits=True))(params, text, img)
        assert logits.shape == (2, cfg.total_seq_len, cfg.vocab_total)
        assert np.isfinite(float(loss))
        assert float(aux["loss_img"]) > 0

    def test_segment_logit_masking(self):
        cfg = tiny_model_config()
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        text = jnp.zeros((1, cfg.text_seq_len), jnp.int32)
        img = jnp.zeros((1, cfg.image_seq_len), jnp.int32)
        _, _, logits = jax.jit(lambda *a: model.apply(
            *a, return_logits=True))(params, text, img)
        logits = np.asarray(logits)
        # text positions: image-vocab logits are -inf-ish
        assert (logits[0, : cfg.text_seq_len, cfg.vocab_text:] < -1e8).all()
        # image positions: text-vocab logits are -inf-ish
        assert (logits[0, cfg.text_seq_len:, : cfg.vocab_text] < -1e8).all()

    def test_weight_sharing_param_count(self):
        """Depth 8 sharing 4 blocks + wconv must create exactly 5 blocks'
        worth of transformer block params (reference task.py:65,78-79)."""
        shared = tiny_model_config(
            text_seq_len=TEXT, image_grid=GRID, depth=8,
            shared_block_cycle=4, final_conv_block=True,
            attn_types=("axial_row", "axial_col", "axial_row", "axial_row"),
            conv_kernel=3)
        unshared = dataclasses.replace(shared, shared_block_cycle=0)
        n_shared = param_count(
            init_params(DALLE(shared), jax.random.PRNGKey(0)))
        n_unshared = param_count(
            init_params(DALLE(unshared), jax.random.PRNGKey(0)))
        # shared: 4 unique + wconv = 5 blocks; unshared: 8 blocks (7 + wconv).
        blocks_params_shared = 5
        blocks_params_unshared = 8
        per_block = (n_unshared - n_shared) / (
            blocks_params_unshared - blocks_params_shared)
        assert per_block > 0
        # consistency: total = base + n_blocks * per_block for both configs
        base_s = n_shared - blocks_params_shared * per_block
        base_u = n_unshared - blocks_params_unshared * per_block
        assert abs(base_s - base_u) < 1e-6

    def test_loss_decreases_under_overfit_signal(self):
        """Sanity: loss on an all-constant batch is lower than on random
        tokens after a few SGD steps (full training-loop test lives in
        test_train.py)."""
        cfg = tiny_model_config()
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(2)
        text = jax.random.randint(rng, (2, cfg.text_seq_len), 0,
                                  cfg.vocab_text)
        img = jax.random.randint(rng, (2, cfg.image_seq_len), 0,
                                 cfg.vocab_image)

        @jax.jit
        def step(p):
            def loss_fn(p):
                loss, _ = model.apply(p, text, img)
                return loss
            loss, grads = jax.value_and_grad(loss_fn)(p)
            p = jax.tree.map(lambda a, g: a - 0.05 * g, p, grads)
            return p, loss

        losses = []
        for _ in range(8):
            params, loss = step(params)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.1

    def test_loss_mask_excludes_padding(self):
        cfg = tiny_model_config()
        model = DALLE(cfg)
        params = init_params(model, jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(3)
        text = jax.random.randint(rng, (1, cfg.text_seq_len), 0,
                                  cfg.vocab_text)
        img = jax.random.randint(rng, (1, cfg.image_seq_len), 0,
                                 cfg.vocab_image)
        mask = jnp.ones((1, cfg.total_seq_len))
        mask = mask.at[:, 2: cfg.text_seq_len].set(0.0)
        loss_m, _ = jax.jit(lambda *a: model.apply(*a, loss_mask=mask))(
            params, text, img)
        loss_f, _ = jax.jit(model.apply)(params, text, img)
        assert np.isfinite(float(loss_m))
        assert float(loss_m) != pytest.approx(float(loss_f))


# The layer scan against the unrolled schedule. Each case is a schedule
# shaped like one the presets run (dim 32, cycle 4, a final conv block):
#   overhang_unroll1  body 9 in 3 x 4 slots: slots 1-3 conditional, and
#                     slot 3 is block_3's first call
#   plain_slot        body 15 in 2 x 8, remat_skip_blocks 1 + save_attn:
#                     the one conditional slot is the plain block (flagship)
#   rematted_slot     the same under blanket remat (XL)
#   even              body 16 in 2 x 8: no slot can be empty
SCAN_CASES = {
    "overhang_unroll1": dict(depth=10),
    "plain_slot": dict(depth=16, scan_unroll=2, remat=True,
                       remat_skip_blocks=1, remat_policy="save_attn"),
    "rematted_slot": dict(depth=16, scan_unroll=2, remat=True,
                          remat_skip_blocks=0),
    "even": dict(depth=17, scan_unroll=2, remat=True,
                 remat_skip_blocks=1, remat_policy="save_attn"),
}
SCAN_CONDITIONAL_SLOTS = {"overhang_unroll1": 3, "plain_slot": 1,
                          "rematted_slot": 1, "even": 0}

# the parameter tree of every case: what checkpoints and the benchmark's
# yardstick read (the parent's paths, written out)
_BLOCK_LEAVES = (
    "attn/k/kernel", "attn/out/bias", "attn/out/kernel", "attn/q/kernel",
    "attn/v/kernel", "attn_norm/bias", "attn_norm/scale", "ff/gate/bias",
    "ff/gate/kernel", "ff/wi/bias", "ff/wi/kernel", "ff/wo/bias",
    "ff/wo/kernel", "ff_norm/bias", "ff_norm/scale")
SCAN_PARAM_PATHS = sorted(
    [f"{block}/{leaf}" for block in (
        "block_wconv", "cycle/block_0", "cycle/block_1", "cycle/block_2",
        "cycle/block_3") for leaf in _BLOCK_LEAVES]
    + ["final_norm/bias", "final_norm/scale"])


@functools.lru_cache(maxsize=None)
def _scan_case(case):
    from dalle_tpu.models.transformer import Transformer
    cfg = tiny_model_config(
        dim=32, heads=2, head_dim=16, shared_block_cycle=4,
        final_conv_block=True,
        attn_types=("axial_row", "axial_col", "axial_row", "full"),
        conv_kernel=3, **SCAN_CASES[case])
    model = Transformer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (2, cfg.total_seq_len, cfg.dim))
    return cfg, model, x, jax.jit(model.init)(jax.random.PRNGKey(1), x)


def _scan_loss(model, x):
    target = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    return lambda params: jnp.mean((model.apply(params, x) - target) ** 2)


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_cycle_matches_unrolled(case):
    """The nn.scan BlockCycle path (the flagship's, with the slot its last
    iteration leaves empty under a conditional) matches the unrolled
    schedule on the loss and on every gradient leaf, given the same
    parameters."""
    import flax
    import flax.linen as nn

    from dalle_tpu.models.transformer import TransformerBlock, _make_rot

    cfg, model, x, params = _scan_case(case)

    class Unrolled(nn.Module):
        @nn.compact
        def __call__(self, x):
            rot = _make_rot(cfg)
            blocks = {}
            for uid, at in cfg.layer_schedule():
                if uid not in blocks:
                    blocks[uid] = TransformerBlock(
                        cfg, at,
                        name="block_wconv" if uid == -1 else f"block_{uid}")
                x = blocks[uid](x, rot)
            return nn.LayerNorm(name="final_norm")(x)

    def without_cycle(tree):
        flat = flax.traverse_util.flatten_dict(tree["params"])
        return {"params": flax.traverse_util.unflatten_dict(
            {p[1:] if p[0] == "cycle" else p: v for p, v in flat.items()})}

    loss, grads = jax.jit(jax.value_and_grad(_scan_loss(model, x)))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        _scan_loss(Unrolled(), x)))(without_cycle(params))
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    got = flax.traverse_util.flatten_dict(without_cycle(grads))
    want = flax.traverse_util.flatten_dict(ref_grads)
    assert sorted(got) == sorted(want)
    for path, g in want.items():
        scale = float(np.abs(np.asarray(g)).max())
        assert scale > 0, path
        np.testing.assert_allclose(
            np.asarray(got[path]), np.asarray(g), rtol=1e-4,
            atol=2e-5 * scale, err_msg="/".join(path))


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_cycle_parameter_tree(case):
    """The conditional changes no parameter's path, also where the
    conditional slot is its block's first call (overhang_unroll1)."""
    import flax
    _, _, _, params = _scan_case(case)
    assert sorted("/".join(p) for p in flax.traverse_util.flatten_dict(
        params["params"])) == SCAN_PARAM_PATHS


def _walk_jaxpr(jaxpr, stack=(), in_cond=False):
    """(primitive, name-stack path, inside a cond, equation) of every
    equation, the nested jaxprs' included (an inner name stack is relative
    to its holder's)."""
    for eqn in jaxpr.eqns:
        path = stack + (str(eqn.source_info.name_stack),)
        yield eqn.primitive.name, "/".join(path), in_cond, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_jaxpr(
                        sub, path, in_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_scan_cycle_conditionals(case):
    """One conditional a direction for each slot that can be empty, none
    on an even schedule, and no select left on the cycle's own path (the
    ``where`` that used to discard every slot's result): the selects that
    remain belong to a block (attention masks) or sit inside a
    conditional's branch."""
    _, model, x, params = _scan_case(case)
    eqns = list(_walk_jaxpr(
        jax.make_jaxpr(jax.grad(_scan_loss(model, x)))(params).jaxpr))
    conds = [(path, eqn) for name, path, inside, eqn in eqns
             if name == "cond" and not inside]
    assert len(conds) == 2 * SCAN_CONDITIONAL_SLOTS[case], conds
    assert all("cycle" in path for path, _ in conds)
    assert sum(name == "scan" for name, *_ in eqns) == 2
    assert not [path for name, path, inside, _ in eqns
                if name == "select_n" and not inside and "cycle" in path
                and "block_" not in path]
    if case == "rematted_slot":
        # a rematted block's residuals are its inputs: nothing but the
        # result leaves the forward conditional (lax.cond's own derivative
        # returns the weights too, and the scan stacks them)
        forward, = [eqn for path, eqn in conds if "transpose" not in path]
        assert len(forward.outvars) == 1


def test_layer_loop_record_of_the_presets():
    """The ``layer_loop`` attribute of the ``setup/warmup`` row, a pure
    function of the configuration (nothing is traced or compiled)."""
    from dalle_tpu.config import (flagship_model_config,
                                  long_context_model_config, xl_model_config)
    from dalle_tpu.models.transformer import layer_loop_record
    overhang = ("63 layers in 8 x 8 slots: 7 always run, 1 conditional "
                "(runs 7 of 8)")
    assert layer_loop_record(flagship_model_config()) == overhang
    assert layer_loop_record(xl_model_config()) == overhang
    assert layer_loop_record(long_context_model_config()) == \
        "64 layers in 16 x 4 slots: all always run"
    assert layer_loop_record(flagship_model_config(scan_unroll=1)) == \
        "63 layers in 16 x 4 slots: 3 always run, 1 conditional (runs 15 of 16)"
    assert layer_loop_record(tiny_model_config(
        depth=4, shared_block_cycle=4)) == "unrolled"
    assert layer_loop_record(tiny_model_config(
        depth=6, shared_block_cycle=0)) == "unrolled"


def test_partial_remat_matches_full_remat():
    """remat_skip_blocks only changes what backward recomputes, never the
    math: loss and grads are identical to blanket remat."""
    import numpy as np

    from dalle_tpu.config import tiny_model_config
    from dalle_init import init_params
    from dalle_tpu.models.dalle import DALLE

    # depth 9 / cycle 4 exercises the scan path (2 repetitions); the
    # unrolled path (reps == 1) is covered by the depth-4 case below
    cfg0 = tiny_model_config(
        depth=9, shared_block_cycle=4, final_conv_block=True,
        attn_types=("axial_row", "axial_col", "axial_row", "axial_row"),
        conv_kernel=3, remat=True)
    cfg1 = type(cfg0)(**{**cfg0.__dict__, "remat_skip_blocks": 2})
    m0, m1 = DALLE(cfg0), DALLE(cfg1)
    params = init_params(m0, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(0, cfg0.vocab_text,
                                   (2, cfg0.text_seq_len)), jnp.int32)
    img = jnp.asarray(rng.randint(0, cfg0.vocab_image,
                                  (2, cfg0.image_seq_len)), jnp.int32)

    def loss_and_grads(m):
        def f(p):
            loss, _ = m.apply(p, text, img)
            return loss
        return jax.jit(jax.value_and_grad(f))(params)

    l0, g0 = loss_and_grads(m0)
    l1, g1 = loss_and_grads(m1)
    assert np.allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_partial_remat_applies_on_unrolled_path():
    """remat_skip_blocks must not be silently ignored when the
    weight-sharing scan is not taken (depth == cycle -> reps == 1)."""
    import numpy as np

    from dalle_tpu.config import tiny_model_config
    from dalle_init import init_params
    from dalle_tpu.models.dalle import DALLE

    cfg0 = tiny_model_config(depth=4, shared_block_cycle=4, remat=True,
                             attn_types=("full",))
    cfg1 = type(cfg0)(**{**cfg0.__dict__, "remat_skip_blocks": 1})
    m0, m1 = DALLE(cfg0), DALLE(cfg1)
    params = init_params(m0, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    text = jnp.asarray(rng.randint(0, cfg0.vocab_text,
                                   (2, cfg0.text_seq_len)), jnp.int32)
    img = jnp.asarray(rng.randint(0, cfg0.vocab_image,
                                  (2, cfg0.image_seq_len)), jnp.int32)

    def grads_of(m):
        def f(p):
            return m.apply(p, text, img)[0]
        return jax.jit(jax.value_and_grad(f))(params)

    (l0, g0), (l1, g1) = grads_of(m0), grads_of(m1)
    assert np.allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # structurally different jaxprs prove the skip actually changed remat
    jp0 = str(jax.make_jaxpr(lambda p: m0.apply(p, text, img)[0])(params))
    jp1 = str(jax.make_jaxpr(lambda p: m1.apply(p, text, img)[0])(params))
    assert jp0.count("remat") != jp1.count("remat")


def test_streaming_head_matches_dense():
    """head_chunk streams the logsumexp over vocab chunks; losses and
    grads must equal the dense head exactly (incl. masked padding rows)."""
    import numpy as np

    from dalle_tpu.config import tiny_model_config
    from dalle_init import init_params
    from dalle_tpu.models.dalle import DALLE

    # vocab sizes deliberately NOT multiples of the chunk: exercises the
    # padded-row masking in the chunked logsumexp
    cfg0 = tiny_model_config(vocab_text=150, vocab_image=70)
    cfg1 = type(cfg0)(**{**cfg0.__dict__, "head_chunk": 64})
    m0, m1 = DALLE(cfg0), DALLE(cfg1)
    params = init_params(m0, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    text = jnp.asarray(rng.randint(0, cfg0.vocab_text,
                                   (3, cfg0.text_seq_len)), jnp.int32)
    img = jnp.asarray(rng.randint(0, cfg0.vocab_image,
                                  (3, cfg0.image_seq_len)), jnp.int32)
    mask = jnp.asarray(rng.rand(3, cfg0.total_seq_len) > 0.2, jnp.float32)

    def loss_and_grads(m):
        def f(p):
            loss, _ = m.apply(p, text, img, loss_mask=mask)
            return loss
        return jax.jit(jax.value_and_grad(f))(params)

    (l0, g0), (l1, g1) = loss_and_grads(m0), loss_and_grads(m1)
    assert abs(float(l0) - float(l1)) < 1e-5
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


class TestXLPreset:
    """BASELINE.json config 5: DALL-E-XL ~3B with VQGAN-f16 tokens."""

    def test_xl_effective_size_and_traceability(self):
        import jax
        import jax.numpy as jnp

        from dalle_tpu.config import xl_model_config
        from dalle_tpu.models.dalle import DALLE, init_params

        cfg = xl_model_config()
        cfg.validate()
        assert cfg.vocab_image == 16384 and cfg.image_grid == 32
        model = DALLE(cfg)
        # eval_shape: parameter census + trace without allocating 3B params
        shapes = jax.eval_shape(
            lambda: init_params(model, jax.random.PRNGKey(0)))
        unique = sum(int(np.prod(x.shape))
                     for x in jax.tree_util.tree_leaves(shapes))
        # unique params (4 shared blocks + w_conv + embeddings)
        assert 0.25e9 < unique < 0.6e9, unique
        # effective size: 64 layer applications over the shared blocks;
        # per layer = 4d^2 attention + 12d^2 GEGLU = 16d^2
        effective = cfg.depth * 16 * cfg.dim * cfg.dim
        assert 2.5e9 < effective < 4.5e9, effective  # the "~3B" claim

        # and the training loss traces end-to-end at the real shape
        text = jax.ShapeDtypeStruct((1, cfg.text_seq_len), jnp.int32)
        image = jax.ShapeDtypeStruct((1, cfg.image_seq_len), jnp.int32)
        out = jax.eval_shape(
            lambda p, t, i: model.apply(p, t, i)[0], shapes, text, image)
        assert out.shape == ()
