"""``KeyeLMConfig`` (preset ``keyevl2``) through models/sparse_lm.py at a
tiny size, seeded random weights, f32, ``index_topk`` under the sequence's
length so that the selection bites and three unequal position rows: the
family's cases over its row (tests/sparse_family.py), and what only it has:
the selected sets are ``lax.top_k`` of the reference's scores, ties to the
lower key; neither loss's gradient reaches the other's leaves; equal rows
are the one-row rotary bit for bit; the sixteen shares add up to the uncut
layer; the Mosaic kernels interpreted against the dense-mask lowering, an
empty tile among the cases; the selection's kernel against ``select_keys``
and ``lax.top_k``, and the lowered step selects by it alone;
``models/decode.py`` refuses the kind."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (KeyeLMConfig, SparseLMConfig,
                              keyevl2_model_config)
from dalle_tpu.models import attention, decode, sparse_lm
from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from dalle_tpu.ops.pallas import indexer_kernels
from sparse_family import as_file, batch, rel_l2

Y = Manifest().yardstick("keye")

# two layers; a sequence of 56 text tokens and a 4 x 4 image field (72: no
# other test file's), so that the three position rows differ; 20 keys a
# query of up to 72; half of the router's experts held. The widths are the
# kernels' (interpreted): 128-wide heads, 64-wide indexer heads, lane tiles
TINY = dict(hidden_size=128, num_hidden_layers=2, num_heads=4,
            num_kv_heads=2, expert_width=128, num_experts=8,
            experts_per_token=2, experts_held=4, expert_offset=2,
            vocab_size=96, text_seq_len=56, image_grid=4, vocab_text=48,
            vocab_image=48, dtype="float32", head_chunk=16, index_topk=20,
            index_heads=2, index_chunk=32)


class TestKeyevl2(fam.Family):
    config, preset = KeyeLMConfig, "keyevl2"
    preset_config, Y = staticmethod(keyevl2_model_config), Y
    # (no ``KERNEL_WIDTHS``: the tiny model's are the kernels')
    TINY, EXPERT_LAYERS, PRECISION = TINY, 2, "highest"
    ADDED = {"index_topk", "index_heads", "index_head_dim", "index_chunk",
             "indexer_rotary", "indexer_loss_weight", "mrope_section"}
    PUBLISHED = dict(
        hidden_size=2048, num_heads=32, num_kv_heads=4, head_dim=128,
        expert_width=768, num_experts=128, experts_per_token=8,
        index_topk=2048, index_heads=16, index_head_dim=64, index_chunk=512,
        mrope_section=(16, 24, 24), rope_theta=1e7, num_hidden_layers=7,
        experts_held=8, vocab_size=18992, score_func="softmax",
        hidden_act="silu", qk_norm=True)
    REFUSAL = ("indexer",)

    def the_yardstick_also(self, *, cfg, aux, grads, ref_grads, weights,
                           text, image, **_):
        with jax.default_matmul_precision("highest"):
            _, (main, align) = Y.loss_fn(weights, text, image, as_file(cfg))
        assert float(aux["loss_main"]) == pytest.approx(float(main), rel=2e-6)
        assert float(aux["loss_indexer"]) == pytest.approx(float(align),
                                                           rel=2e-5)
        assert float(align) > 0.01                  # the second loss is there
        assert float(aux["loss"]) == pytest.approx(
            float(aux["loss_main"]) + cfg.indexer_loss_weight
            * float(aux["loss_indexer"]), rel=1e-6)
        t, k = cfg.total_seq_len, cfg.index_topk
        pairs = sum(min(i + 1, k) for i in range(t))
        assert float(aux["sparse_selected_pct"]) == pytest.approx(
            100.0 * pairs / (t * (t + 1) / 2), rel=1e-6)
        ours, theirs = fam.leaves(grads), fam.leaves(ref_grads)
        assert sum("['indexer']" in name for name in ours) == 5 * 2
        for name in ours:       # every leaf counts, the indexer's too
            assert float(jnp.abs(theirs[name]).max()) > 0, name

    def the_normal_path_also(self, *, task, names, warm, steps, **_):
        """The rows carry the two losses, the selected share and the
        model's records, ``sparse_layout`` among them."""
        assert sum("['indexer']" in name for name in names) == 5 * 2
        assert warm["attn_layout"].startswith(
            "over 20 keys a query, chosen by an indexer of 2 heads of 64 over "
            "one key head, dense XLA lowering (no Mosaic backend)")
        assert "three rows by sections [16, 24, 24]" in warm["attn_layout"]
        assert warm["sparse_layout"] == (
            "dense masks in XLA code (no Mosaic backend)")
        assert "softmax over the chosen" in warm["moe_layout"]
        t, k = 72, 20
        share = 100.0 * sum(min(i + 1, k) for i in range(t)) / (
            t * (t + 1) / 2)
        for row in steps:
            assert row["loss_indexer"] > 0 and row["loss_main"] > 0
            assert row["sparse_selected_pct"] == pytest.approx(share, rel=1e-5)
        assert sparse_lm.step_attributes(task.model_cfg)[-3:] == (
            "loss_main", "loss_indexer", "sparse_selected_pct")
        assert sparse_lm.step_attributes(SparseLMConfig())[-1] == \
            "moe_tiles_active_pct"

    def the_class_also(self, cfg, flags):
        assert {cfg.kind_of_layer(i) for i in range(7)} == {"selected_rope"}
        assert not any(cfg.layer_is_dense(i) for i in range(7))
        assert not (cfg.attention_gate or cfg.sandwich_norms or cfg.mup_enabled
                    or cfg.num_shared_experts or cfg.kv_lora_rank
                    or cfg.tied_embeddings or cfg.selection_bias)
        assert "index_topk" in flags and "mrope_section" not in flags
        # the kind needs a class that states an indexer
        with pytest.raises(ValueError, match="selected_rope"):
            SparseLMConfig(layer_kinds=("selected_rope",)).validate()
        with pytest.raises(ValueError, match="mrope_section"):
            dataclasses.replace(cfg, mrope_section=(16, 24, 20)).validate()
        with pytest.raises(ValueError, match="rotates the indexer"):
            dataclasses.replace(cfg, indexer_rotary=False).validate()
        # what init_params counts: PERF.md section 4
        shapes = jax.eval_shape(lambda: sparse_lm.init_params(
            sparse_lm.build(cfg), jax.random.PRNGKey(0)))
        assert sum(a.size for a in jax.tree.leaves(shapes)) == 491_848_320
        layer = shapes["params"]["layer_0"]
        count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
        assert count(layer["attn"]["indexer"]) == 2_260_992 + 128
        assert count(layer["ff"]["experts"]) == 37_748_736


def test_the_selected_sets_are_top_k_of_the_references_scores():
    """The program's selection on its own dense scores against the
    yardstick's sets, layer by layer, rows with fewer than ``index_topk``
    keys before them included (they choose every one)."""
    cfg = KeyeLMConfig(**TINY)
    params, (text, image) = fam.params(cfg), batch(cfg)
    model = sparse_lm.build(cfg)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(Y.chosen_keys(params, text, image, as_file(cfg)))
        _, kept = jax.jit(lambda *a: model.apply(
            *a, mutable=["intermediates"],
            capture_intermediates=lambda m, _: isinstance(
                m, sparse_lm.Indexer)))(params, text, image)
        scale = (cfg.index_heads * cfg.index_head_dim) ** -0.5
        for i in range(cfg.num_hidden_layers):
            qi, ki, w = kept["intermediates"][f"layer_{i}"]["attn"][
                "indexer"]["__call__"][0]
            sel = sparse_lm.select_keys(
                sparse_lm.dense_index_scores(qi, ki, w, scale),
                cfg.index_topk, cfg.index_chunk)
            ours = np.asarray(sel > sparse_lm.OFF)
            assert (ours == theirs[i]).all(), i
            count = ours.sum(-1)
            assert (count == np.minimum(np.arange(cfg.total_seq_len) + 1,
                                        cfg.index_topk)).all()
            assert not np.triu(ours[0], 1).any()         # never a later key


@pytest.mark.parametrize("topk, chunk", [(5, 8), (16, 16), (7, 64), (40, 8)])
def test_select_keys_is_lax_top_k_ties_to_the_lower_key(topk, chunk):
    """Scores drawn from few values, so that ties straddle most rows'
    thresholds, and a -0.0 beside a 0.0: the chosen keys are those of
    ``lax.top_k`` over the keys up to the query (stable: the lower index
    first), and the array holds their scores and ``OFF`` elsewhere."""
    t = 40
    rng = np.random.default_rng(topk)
    scores = rng.integers(-2, 3, (2, t, t)).astype(np.float32) * 0.5
    scores[0, :, 3] = -0.0
    sel = np.asarray(sparse_lm.select_keys(jnp.asarray(scores), topk, chunk))
    for b in range(2):
        for q in range(t):
            k = min(topk, q + 1)
            _, idx = jax.lax.top_k(jnp.asarray(scores[b, q, :q + 1]), k)
            want = np.zeros(t, bool)
            want[np.asarray(idx)] = True
            assert ((sel[b, q] > sparse_lm.OFF) == want).all(), (b, q)
            assert (sel[b, q][want] == scores[b, q][want]).all()
            assert (sel[b, q][~want] == sparse_lm.OFF).all()


@pytest.mark.parametrize("with_kernels", [False, True])
def test_neither_losss_gradient_reaches_the_others_leaves(with_kernels,
                                                          monkeypatch):
    """``d loss_main / d indexer`` and ``d loss_indexer / d (every other
    leaf)`` are nought exactly: the indexer reads its input with the
    gradient stopped and chooses, which no gradient passes; its loss's
    target is the heads' mean with the gradient stopped."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", with_kernels)
    cfg = KeyeLMConfig(**TINY)
    params, (text, image) = fam.params(cfg), batch(cfg)
    model = sparse_lm.build(cfg)
    of = lambda name: jax.jit(jax.grad(
        lambda p: model.apply(p, text, image)[1][name]))(params)
    main, align = (fam.leaves(of(name))
                   for name in ("loss_main", "loss_indexer"))
    for name in main:
        if "['indexer']" in name:
            assert not np.asarray(main[name]).any(), name
            assert np.asarray(align[name]).any(), name
        else:
            assert not np.asarray(align[name]).any(), name
            assert np.asarray(main[name]).any(), name


def test_equal_rows_are_the_one_row_rotary_bit_for_bit():
    """Three equal position rows give ``rotary_cos_sin``'s tables and
    ``head_pass``'s one-row result, to the last bit; unequal rows do not,
    and each frequency pair reads the row its section names."""
    t, d, theta, sections = 24, 128, 1e7, (16, 24, 24)
    rows = jnp.broadcast_to(jnp.arange(t), (3, t))
    for heads in (1, 3):
        got = sparse_lm.position_tables(rows, sections, d, theta, heads)
        want = attention.rotary_cos_sin(jnp.arange(t), d, theta, heads)
        for a, b in zip(got, want):
            assert (np.asarray(a) == np.asarray(b)).all()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, t, 2 * d))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d,))
    how = dict(mesh=None, eps=1e-6, head_dim=d, theta=theta)
    one = sparse_lm.head_pass(x, scale, **how)
    same = sparse_lm.head_pass(x, scale, positions=rows, sections=sections,
                               **how)
    assert (np.asarray(one) == np.asarray(same)).all()
    apart = sparse_lm.field_positions(16, 8, 4)
    assert apart.shape == (3, t)
    assert apart[:, :16].tolist() == [list(range(16))] * 3
    assert apart[0, 16:].tolist() == [16] * 8
    assert apart[1, 16:].tolist() == [16] * 4 + [17] * 4
    assert apart[2, 16:].tolist() == [16, 17, 18, 19] * 2
    other = sparse_lm.head_pass(x, scale, positions=apart,
                                sections=sections, **how)
    assert not (np.asarray(one)[:, 16:] == np.asarray(other)[:, 16:]).all()
    assert (np.asarray(one)[:, :16] == np.asarray(other)[:, :16]).all()
    # pair i of 64 reads row 0 for i < 16, row 1 to 40, row 2 after
    cos, _ = sparse_lm.position_tables(apart, sections, d, theta)
    freqs = 1.0 / theta ** (np.arange(64) / 64)
    for pair, row in ((0, 0), (15, 0), (16, 1), (39, 1), (40, 2), (63, 2)):
        want = np.cos(np.asarray(apart[row], np.float32) * np.float32(
            freqs[pair]))
        np.testing.assert_allclose(cos[:, pair], want, atol=1e-6)
        np.testing.assert_allclose(cos[:, 64 + pair], want, atol=1e-6)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The preset's deployment at a small width: 128 experts, top 8, over
    16 shares of 8 consecutive experts. Every share's layer returns its
    routed part and nothing else: the sixteen summed as they come equal the
    reference's uncut layer, and every assignment is computed by one; two
    of the shares through the grouped kernels (interpreted) are the dense
    lowering's."""
    base = KeyeLMConfig(**dict(TINY, num_experts=128, experts_per_token=8,
                               experts_held=8, expert_offset=0))
    n, held = base.num_experts, base.experts_held
    assert n // held == 16
    rng = jax.random.split(jax.random.PRNGKey(3), 5)
    d, f = base.hidden_size, base.expert_width
    m = jax.random.normal(rng[0], (2, 36, d))
    whole = {"router": jax.random.normal(rng[1], (d, n)),
             "experts": {"gate": jax.random.normal(rng[2], (n, d, f)) * 0.2,
                         "up": jax.random.normal(rng[3], (n, d, f)) * 0.2,
                         "down": jax.random.normal(rng[4], (n, f, d)) * 0.2}}

    def share(i):
        cfg = dataclasses.replace(base, expert_offset=held * i)
        layer = sparse_lm.ExpertLayer(cfg)
        mine = {"params": dict(whole, experts={
            k: w[held * i: held * (i + 1)]
            for k, w in whole["experts"].items()})}
        idx, p = layer.apply(mine, m, method="route")   # alike on all
        y, counters = layer.apply(mine, m, idx, p)
        return y, float(counters["here"]), p

    with jax.default_matmul_precision("highest"):
        want = Y.whole_layer_experts(m, whole, as_file(base))
        parts = [share(i) for i in range(16)]
        total = sum(y for y, _, _ in parts)
        np.testing.assert_allclose(total, want, atol=1e-4)
        # every assignment, by one share
        assert sum(here for _, here, _ in parts) == pytest.approx(1.0)
        np.testing.assert_allclose(jnp.sum(parts[0][2], -1), 1.0, atol=1e-6)
        monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
        for i in (0, 9):
            np.testing.assert_allclose(share(i)[0], parts[i][0], atol=1e-4)


# -- the kernels, interpreted, against the dense-mask lowering ---------------

def _operands(t, heads, kv_heads, index_heads, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, width: jax.random.normal(k, (2, t, width))
    return (normal(keys[0], heads * 128), normal(keys[1], kv_heads * 128),
            normal(keys[2], kv_heads * 128), normal(keys[3],
                                                    index_heads * 64),
            normal(keys[4], 64), normal(keys[5], index_heads))


def _dense_attention(q, k, v, sel):
    b, t, _ = q.shape
    g = k.shape[2] // 128
    s = jnp.einsum("bqgnd,bkgd->bgnqk", q.reshape(b, t, g, -1, 128),
                   k.reshape(b, t, g, 128)) * 128 ** -0.5
    prob = jax.nn.softmax(jnp.where((sel > sparse_lm.OFF)[:, None, None], s,
                                    -1e30), -1)
    out = jnp.einsum("bgnqk,bkgd->bqgnd", prob, v.reshape(b, t, g, 128))
    return out.reshape(q.shape), jnp.mean(prob, axis=(1, 2))


def _an_empty_tile(sel):
    """Queries 128.. choose among the keys 128.. only, themselves at least
    (a set is never empty): the tile (query block 1, key block 0) holds no
    chosen pair."""
    sel = np.array(sel)
    sel[:, 128:, :128] = sparse_lm.OFF
    late = np.arange(128, sel.shape[1])
    sel[:, late, late] = 0.25
    return jnp.asarray(sel)


@pytest.mark.parametrize("t, heads, kv_heads, topk, empty", [
    (256, 4, 2, 40, False), (256, 2, 2, 40, True), (200, 4, 1, 64, False)])
def test_the_selected_kernels_are_the_dense_mask_lowering(
        t, heads, kv_heads, topk, empty):
    """Forward, statistics' use (the heads' mean) and backward of the three
    attention kernels at blocks of 128, T a whole number of blocks and not,
    a tile with no chosen key among the cases."""
    q, k, v, qi, ki, w = _operands(t, heads, kv_heads, 2, seed=t + topk)
    with jax.default_matmul_precision("highest"):
        sel = sparse_lm.select_keys(
            sparse_lm.dense_index_scores(qi, ki, w, 0.1), topk, 64)
        if empty:
            sel = _an_empty_tile(sel)
            assert not (np.asarray(sel)[:, 128:, :128]
                        > sparse_lm.OFF).any()
        want, want_mean = _dense_attention(q, k, v, sel)
        (out, stats), back = jax.vjp(
            lambda *a: kernels.selected_attention(*a, sel, 128, True),
            q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-5)
        mean = kernels.selected_mean_probs(q, k, stats, sel, 128,
                                           True)[:, :t, :t]
        on = np.asarray(sel > sparse_lm.OFF)
        np.testing.assert_allclose(np.where(on, mean, 0),
                                   np.where(on, want_mean, 0), atol=2e-6)
        dout = jax.random.normal(jax.random.PRNGKey(9), out.shape)
        got = back((dout, jnp.zeros_like(stats)))
        _, dense_back = jax.vjp(
            lambda *a: _dense_attention(*a, sel)[0], q, k, v)
        for ours, theirs in zip(got, dense_back(dout)):
            assert rel_l2(ours, theirs) < 2e-5


def _every_key_before(sel):
    """Every row chooses every key up to itself (a sequence's rows before
    ``index_topk``): scores of its own, the largest finite f32 among them."""
    t = sel.shape[1]
    x = np.random.default_rng(t).standard_normal(sel.shape).astype(
        np.float32) * 4.0
    x[:, :, 7] = 30.0
    return jnp.asarray(np.where(np.tril(np.ones((t, t), bool)), x,
                                sparse_lm.OFF))


@pytest.mark.parametrize("t, heads, kv_heads, topk, planted", [
    (256, 4, 2, 40, None), (256, 2, 2, 40, _an_empty_tile),
    (200, 4, 1, 64, None), (384, 4, 2, 300, None),
    (200, 2, 1, 200, _every_key_before)])
def test_the_loss_rows_are_log_softmax_and_the_dense_kl(t, heads, kv_heads,
                                                        topk, planted):
    """The rows form of the heads' mean's kernel at blocks of 128, T a whole
    number of blocks and not, a tile with no chosen key, rows before
    ``index_topk`` and sets that are every key before the query: a row's KL
    against the dense one over the mean form's array, its log-sum-exp
    against ``logsumexp`` over the set, its count; f32 all."""
    q, k, v, qi, ki, w = _operands(t, heads, kv_heads, 2, seed=t + topk)
    with jax.default_matmul_precision("highest"):
        sel = sparse_lm.select_keys(
            sparse_lm.dense_index_scores(qi, ki, w, 0.1), topk, 64)
        if planted is not None:
            sel = planted(sel)
        on = sel > sparse_lm.OFF
        _, stats = kernels.selected_forward(q, k, v, sel, 128, True)
        rows = kernels.selected_loss_rows(q, k, stats, sel, 128, True)
        assert rows.shape == (2, t + -t % 128, 128) and rows.dtype == \
            jnp.float32
        pbar = jnp.where(on, kernels.selected_mean_probs(
            q, k, stats, sel, 128, True)[:, :t, :t], 0.0)
        log_sigma = jax.nn.log_softmax(jnp.where(on, sel, sparse_lm.OFF), -1)
        want = jnp.sum(jnp.where(on, jax.scipy.special.xlogy(pbar, pbar)
                                 - pbar * log_sigma, 0.0), -1)
        lse = jax.nn.logsumexp(jnp.where(on, sel, -jnp.inf), axis=-1)
    rows = np.asarray(rows)
    kl = rows[:, :t, kernels.KL_LANE]
    np.testing.assert_allclose(kl, want, atol=1e-5, rtol=1e-5)
    assert kl.min() > -1e-5 and kl.max() > 0.1      # (f32 sums: no clamp)
    np.testing.assert_allclose(rows[:, :t, kernels.LSE_LANE], lse,
                               atol=4e-6, rtol=1e-6)
    count = np.minimum(np.arange(t) + 1, topk)
    assert np.array_equal(np.asarray(on).sum(-1), np.broadcast_to(
        count, (2, t))) or planted is _an_empty_tile
    assert np.array_equal(rows[:, :t, kernels.COUNT_LANE],
                          np.asarray(on).sum(-1))
    # a padded row: no key, no loss, a finite log-sum-exp
    assert not rows[:, t:, kernels.KL_LANE].any()
    assert not rows[:, t:, kernels.COUNT_LANE].any()
    assert np.isfinite(rows).all()


@pytest.mark.parametrize("t, index_heads", [(256, 2), (200, 4), (384, 16)])
def test_the_indexers_kernels_are_the_dense_scores_and_their_gradient(
        t, index_heads):
    """``index_scores`` on the causal band's tiles, and ``index_grads``
    against plain differentiation of the KL through the dense scores."""
    q, k, _, qi, ki, w = _operands(t, 2, 1, index_heads, seed=t)
    scale = (index_heads * 64) ** -0.5
    with jax.default_matmul_precision("highest"):
        dense = sparse_lm.dense_index_scores(qi, ki, w, scale)
        scores = indexer_kernels.index_scores(qi, ki, w, scale, 128, True)
        band = np.tril(np.ones((t, t), bool))
        np.testing.assert_allclose(
            np.where(band, scores[:, :t, :t], 0), np.where(band, dense, 0),
            atol=2e-5)
        sel = sparse_lm.select_keys(dense, 48, 64)
        on = sel > sparse_lm.OFF
        pbar = jax.nn.softmax(jnp.where(on, jax.random.normal(
            jax.random.PRNGKey(5), sel.shape), -1e30), -1)
        coef = jax.random.uniform(jax.random.PRNGKey(6), (2, t))

        def loss(qi, ki, w):
            log_sigma = jax.nn.log_softmax(jnp.where(
                on, sparse_lm.dense_index_scores(qi, ki, w, scale),
                sparse_lm.OFF), -1)
            return jnp.sum(coef * jnp.sum(
                jnp.where(on, -pbar * log_sigma, 0.0), -1))
        want = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(qi, ki, w)
        lse = jax.nn.logsumexp(jnp.where(on, sel, -jnp.inf), axis=-1)
        got = indexer_kernels.index_grads(qi, ki, w, lse, coef, sel, pbar,
                                          scale, 128, True)
    for ours, theirs in zip(got, want):
        assert ours.shape == theirs.shape
        assert rel_l2(ours, theirs) < 2e-5


# -- the selection's kernel, interpreted --------------------------------------

BIG = float(np.finfo(np.float32).max)


def _few(seed, b, t):
    """Scores of five values, so that ties straddle most rows' thresholds."""
    return np.random.default_rng(seed).integers(-2, 3, (b, t, t)).astype(
        np.float32) * 0.5


def _normal(seed, b, t):
    return np.random.default_rng(seed).standard_normal((b, t, t)).astype(
        np.float32)


def _zeros(seed, b, t):
    """Runs of exact zeros under a row's threshold, a -0.0 beside a 0.0."""
    x = np.maximum(_normal(seed, b, t), 0.0)
    x[:, :, 3::7] = -0.0
    return x


def _extremes(seed, b, t):
    """Negative scores all, the largest and the least finite f32 among
    them, twice each (a tie at either end)."""
    x = -np.abs(_normal(seed, b, t)) - 1.0
    x[:, :, 5] = x[:, :, 90] = BIG
    x[:, :, 6] = x[:, :, 70] = -BIG
    return x


def _tied_rows(seed, b, t):
    """Distinct scores but in the last row block and in the first that
    searches (rows 100-130 at 100 keys a query), where a row's threshold is
    tied across more keys than the row may take."""
    x = _normal(seed, b, t)
    for rows in (slice(100, 131), slice(t - 20, t)):
        x[:, rows] = np.round(x[:, rows])
    return x


def _top_k_sets(x, topk):
    """(B, T, T) bool: ``lax.top_k``'s sets over the keys up to the query."""
    b, t, _ = x.shape
    causal = np.tril(np.ones((t, t), bool))
    _, idx = jax.lax.top_k(jnp.where(causal, x, -jnp.inf), min(topk, t))
    keep = np.arange(idx.shape[-1])[None, :] < np.minimum(
        np.arange(t) + 1, topk)[:, None]
    on = np.zeros((b, t, t), bool)
    for i in range(b):
        rows = np.broadcast_to(np.arange(t)[:, None], keep.shape)[keep]
        on[i, rows, np.asarray(idx[i])[keep]] = True
    return on


@pytest.mark.parametrize("scores, b, t, topk, block", [
    (_normal, 1, 256, 200, 128),      # rows before index_topk: a block, and
                                      # a block that holds some
    (_normal, 2, 200, 64, 128),       # T no whole number of row blocks; B 2
    (_few, 1, 200, 64, 128),          # ... and ties everywhere
    (_tied_rows, 1, 384, 100, 128),   # ties in the first searching block
                                      # and in the last
    (_zeros, 1, 256, 40, 128),
    (_extremes, 1, 256, 40, 128),
    (_few, 2, 256, 300, 128),         # index_topk >= T: no search
    (_normal, 1, 700, 130, 512),      # T no whole number of BLOCK; row
                                      # blocks of 128 in a block of 512
    (_few, 1, 130, 7, 128),           # few keys a query, most rows tied
    (_normal, 1, 4200, 4150, 512),    # keys past one group of bit planes
])
def test_the_selection_kernel_is_select_keys_and_lax_top_k(scores, b, t,
                                                           topk, block):
    """``index_select`` on an array whose tiles above the causal band hold
    NaN (``index_scores`` leaves them unwritten): ``select_keys``' array bit
    for bit, the sets those of ``lax.top_k`` over the keys up to the query
    (ties to the lower key), the score on a set and ``OFF`` off it."""
    x = scores(t + topk, b, t)
    pad = -t % block
    tile = np.arange(t + pad) // block
    padded = np.where(tile[None, :] > tile[:, None], np.nan,
                      np.pad(x, ((0, 0), (0, pad), (0, pad))))
    sel = np.asarray(indexer_kernels.index_select(
        jnp.asarray(padded, jnp.float32), topk, block, True))
    assert sel.shape == padded.shape
    assert (sel[:, :, t:] == sparse_lm.OFF).all()    # every column written
    sel = sel[:, :t, :t]
    want = np.asarray(sparse_lm.select_keys(jnp.asarray(x), topk, 64))
    assert np.array_equal(sel, want)
    on = _top_k_sets(jnp.asarray(x), topk)
    # (the least finite f32 lies under OFF: a set is told by equality)
    assert np.array_equal(sel[on].view(np.int32), x[on].view(np.int32))
    assert (sel[~on] == sparse_lm.OFF).all()
    assert (on.sum(-1) == np.minimum(np.arange(t) + 1, topk)).all()


def test_the_bit_planes_are_the_words_transposed():
    words = np.random.default_rng(0).integers(
        -2 ** 31, 2 ** 31, (32, 8, 128)).astype(np.int32)
    planes = np.asarray(indexer_kernels._planes(jnp.asarray(words)))
    for bit in range(32):
        for j in (0, 1, 13, 30, 31):
            assert np.array_equal((planes[31 - bit] >> (31 - j)) & 1,
                                  (words[j] >> bit) & 1)


def test_the_derivative_rule_with_the_selection_kernel_is_the_dense_lowering(
        monkeypatch):
    """``_selected_kernels`` (scores, selection, attention, the heads' mean,
    the loss's gradient: every kernel interpreted) against
    ``dense_selected_attention`` differentiated plainly: the three results
    and all six cotangents."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    operands = _operands(200, 4, 2, 2, seed=53)
    topk, chunk, scale = 48, 64, 0.1
    with jax.default_matmul_precision("highest"):
        ours, back = jax.vjp(lambda *a: sparse_lm._selected_kernels(
            *a, topk, chunk, scale), *operands)
        theirs, dense_back = jax.vjp(
            lambda *a: sparse_lm.dense_selected_attention(
                *a, topk=topk, chunk=chunk, scale=scale, head_dim=128),
            *operands)
        np.testing.assert_allclose(ours[0], theirs[0], atol=2e-5)
        np.testing.assert_allclose(ours[1], theirs[1], rtol=2e-5)
        assert np.array_equal(ours[2], theirs[2])            # chosen pairs
        keys = jax.random.split(jax.random.PRNGKey(8), 2)
        cotangents = (jax.random.normal(keys[0], ours[0].shape),
                      jax.random.uniform(keys[1], ours[1].shape),
                      jnp.zeros_like(ours[2]))
        for name, mine, plain in zip("q k v qi ki w".split(),
                                     back(cotangents),
                                     dense_back(cotangents)):
            assert rel_l2(mine, plain) < 2e-5, name


@pytest.mark.parametrize("kept", [True, False])
def test_a_rematerialised_layer_keeps_the_rows_log_sum_exp(monkeypatch, kept):
    """The compiled grad step, every kernel interpreted, runs the rows form
    of the heads' mean's kernel once a layer (the forward pass) and the mean
    form once (the backward rule): the layer's replay does not make the
    rows again, because their log-sum-exp is kept under the statistics'
    name. Each form's result passes through a host callback that counts,
    which the compiler drops with a result nothing reads. ``kept`` False:
    the name left off the (B, T) residual, and the replay runs the rows
    form a second time (what the name is for, and that the count tells)."""
    import collections

    from dalle_tpu.training.steps import make_grad_step

    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    ran = collections.Counter()

    def counted(form):
        def seen(x):
            ran[form.__name__] += 1
            return x

        def through(*operands):
            out = form(*operands)
            return jax.pure_callback(
                seen, jax.ShapeDtypeStruct(out.shape, out.dtype), out)
        through.__name__ = form.__name__
        return through

    for form in (kernels.selected_loss_rows, kernels.selected_mean_probs):
        monkeypatch.setattr(kernels, form.__name__, counted(form))
    if not kept:
        named = sparse_lm.checkpoint_name
        monkeypatch.setattr(
            sparse_lm, "checkpoint_name",
            lambda x, name: x if x.ndim == 2 else named(x, name))
    cfg = KeyeLMConfig(**TINY)
    (text, image) = batch(cfg)
    jax.block_until_ready(jax.jit(make_grad_step(
        sparse_lm.build(cfg), accum_steps=1))(
            fam.params(cfg), {"text": text, "image": image}))
    layers = cfg.num_hidden_layers
    assert ran == {"selected_loss_rows": layers * (1 if kept else 2),
                   "selected_mean_probs": layers}


def test_the_lowered_step_selects_by_the_kernel_alone(monkeypatch):
    """A small grad step lowered for a TPU with the Mosaic lowering:
    ``_index_select_kernel`` wherever the scores are made (a layer's
    forward, its replay, its backward rule), and nothing under the scope
    ``attn/indexer/select`` but the kernel and the cut to T: no loop of
    ``select_keys``."""
    import re

    from benchmark.harness import kernel_census
    from dalle_tpu.training.steps import make_grad_step

    cfg = KeyeLMConfig(**TINY)
    model = sparse_lm.build(cfg)
    shapes = jax.eval_shape(
        lambda: sparse_lm.init_params(model, jax.random.PRNGKey(0)))
    tokens = lambda n: jax.ShapeDtypeStruct((2, n), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(make_grad_step(model, accum_steps=2)).trace(
        shapes, {"text": tokens(cfg.text_seq_len),
                 "image": tokens(cfg.image_seq_len)}).lower(
                     lowering_platforms=("tpu",)).as_text(debug_info=True)
    found = kernel_census(text)
    assert found["_index_select_kernel"] == found["_index_scores_kernel"] \
        == 3 * cfg.num_hidden_layers
    assert found["_index_grads_kernel"] == cfg.num_hidden_layers
    under = set(re.findall(r'attn/indexer/select/([^"/]*)', text))
    assert under == {"pallas_call", "slice"}
    # the loss's rows are summed in the mean's kernel: no XLA reduction,
    # slice or loop under the alignment's scope, and its two forms where
    # the scores are made
    assert found["_selected_mean_kernel"] == 3 * cfg.num_hidden_layers
    under = set(re.findall(r'attn/indexer/align/([^"/]*)', text))
    # (the tiny T padded to whole blocks: ``jnp.pad``; not at the cell's)
    assert under - {"jit", "jit(_pad)", "pad"} == {"pallas_call"}


def test_the_predicates_say_why_not():
    assert kernels.selected_fits(8192, 4096, 512, 128, 2) is None
    assert "head_dim 64" in kernels.selected_fits(8192, 2048, 512, 64, 2)
    assert "MiB of VMEM" in kernels.selected_fits(65536, 4096, 512, 128, 2)
    assert indexer_kernels.fits(8192, 16, 64, 2) is None
    assert "two a lane tile" in indexer_kernels.fits(8192, 16, 128, 2)
    assert "pairs" in indexer_kernels.fits(8192, 3, 64, 2)
    # the selection's row block: its scores, its selection, their bit planes
    assert indexer_kernels.fits(24576, 16, 64, 2) is None
    refused = indexer_kernels.fits(28672, 16, 64, 2)
    assert "a row block of 128 queries' scores over 28672 keys" in refused
    assert "MiB of VMEM" in refused and refused.count(".") == 1
    # the one-kernel backward with the selection's tile: 2 MiB more
    assert kernels.fused_backward_fits(8192, 8, 2) is None
    assert kernels.fused_backward_fits(8192, 8, 2, selected=True) is None



def test_decode_refuses_the_kind_by_name():
    with pytest.raises(NotImplementedError) as refused:
        decode.init_cache(keyevl2_model_config(), batch=1)
    message = str(refused.value)
    assert "'selected_rope'" in message and "indexer" in message
    assert message.count(".") == 1 and "\n" not in message  # decode.py only
    decode.refuse_selected_layers(SparseLMConfig())         # no such layer
