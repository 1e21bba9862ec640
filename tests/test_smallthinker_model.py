"""The sparse language model (models/sparse_lm.py) at a tiny size, seeded
random weights, f32, as ``SparseLMConfig`` (preset ``smallthinker21b``), the
root of the family's classes: the family's cases over its row
(tests/sparse_family.py), and what only it has: the shares of the expert
layer add up to the uncut layer; no token is dropped whatever the router
does; the token-major sums and the expert block by both lowerings."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.cli import run_trainer
from dalle_tpu.config import ModelConfig, SparseLMConfig
from dalle_tpu.config import smallthinker21b_model_config
from dalle_tpu.models import attention, family, sparse_lm
from dalle_tpu.ops.pallas import grouped_matmul_kernels as grouped
from dalle_tpu.ops.pallas import token_sum_kernels as token_sum
from sparse_family import as_file, batch, rel_l2

Y = Manifest().yardstick("smallthinker")

# both layer kinds, a sequence (28) longer than the window (8), half of the
# router's experts held
TINY = dict(hidden_size=64, num_hidden_layers=4, num_heads=4, num_kv_heads=2,
            head_dim=16, expert_width=32, num_experts=8, experts_per_token=2,
            experts_held=4, expert_offset=2, vocab_size=96, window=8,
            text_seq_len=12, image_grid=4, vocab_text=48, vocab_image=48,
            dtype="float32", head_chunk=16)
# the attention's blockwise Pallas kernels, interpreted
KERNEL_WIDTHS = dict(head_dim=128)


class TestSmallthinker21b(fam.Family):
    config, preset = SparseLMConfig, "smallthinker21b"
    preset_config, Y = staticmethod(smallthinker21b_model_config), Y
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 4
    MOVED = False       # the weights as ``init_params`` draws them
    # ... and the band of its one padded tile, an edge in sub-tiles
    BLOCKWISE = {"full_nope": (None, 256), "window_rope": (8, 256)}
    ADDED = set()       # the root: the family's own fields
    # every width is the source's (``reduced``: depth, experts held, vocab)
    PUBLISHED = dict(hidden_size=2560, num_heads=28, num_kv_heads=4,
                     head_dim=128, expert_width=768, num_experts=64,
                     experts_per_token=6, window=4096, rope_theta=1.5e6,
                     rms_eps=1e-6)
    REFUSAL = ("grouped key-value heads", "expert layer")

    def the_yardstick_also(self, *, cfg, aux, with_kernels, lowering_record,
                           said, **_):
        assert {cfg.kind_of_layer(i) for i in range(4)} == {"full_nope",
                                                           "window_rope"}
        # the two fields' means weigh back to the loss: 11 and 16 targets
        assert float(aux["loss"]) == pytest.approx(
            (11 * float(aux["loss_text"]) + 16 * float(aux["loss_img"])) / 27,
            rel=1e-6)
        # (28 tokens: the rotary's pass wants rows in eights, the test below)
        assert lowering_record.first_refusal(
            ("rotary", (28, heads * cfg.head_dim, cfg.head_dim))
            for heads in (4, 2)) == (
                "28 rows are not whole sublane tiles of 8" if with_kernels else
                "no Mosaic backend")
        assert said["attn_layout"] == (
            f"blockwise 512: {4 * with_kernels} of 4 layers, 1 full no-rope + "
            "3 window 8 rope, 2 query heads a key-value head"
            + (", backward: one kernel a tile (4 of 4 layers), rotary (XLA: "
               "28 rows are not whole sublane tiles of 8)" if with_kernels else
               ", rotary (XLA: no Mosaic backend)"))
        # the band's account, a layer kind, from the function the kernels use
        assert said.get("attn_band") == (
            "1 full_nope: 1 tile, 1 at an edge by sub-tiles of 256, visited "
            "over allowed pairs 1.9961 -> 1.4971; 3 window_rope: 1 tile, 1 at "
            "an edge by sub-tiles of 256, visited over allowed pairs 64.4405 "
            "-> 48.3304" if with_kernels else None)

    def the_normal_path_also(self, *, warm, steps, **_):
        """The sentences, whole, as the operator reads them."""
        assert type(run_trainer.configs_from_args(
            run_trainer.build_parser().parse_args(["--preset", "tiny"]))[0]) \
            is ModelConfig
        assert warm["moe_layout"] == (
            "4 of 8 experts held (2-5), top 2 of 8, softmax over the chosen, "
            "no exchange: 8 devices, data parallel; token-major sums: none "
            "traced (the dense lowering)")
        assert warm["attn_layout"] == (
            "blockwise 512: 0 of 4 layers, 1 full no-rope + 3 window 8 rope, "
            "2 query heads a key-value head, rotary (XLA: no Mosaic backend)")
        assert warm["layer_loop"] == (
            "unrolled: 4 layers, each rematerialised but its attention")
        for row in steps:
            assert 0 < row["moe_assignments_here_pct"] < 100
            assert row["moe_load_max_over_mean"] >= 1.0
            assert row["moe_sum_spills"] == 0.0      # a dense call has no runs
            assert row["moe_tiles_active_pct"] == 0.0    # and no grid of tiles


@pytest.mark.parametrize("interpret, budget, words", [
    (True, None, ", backward: one kernel a tile (4 of 4 layers)"),
    (True, 2 ** 20, ", backward: dq + dk/dv kernels (dk and dv of 512 "
     "tokens need 11.0 MiB of VMEM, over 1)"),
    (False, None, ""),          # no blockwise kernel, so no backward of it
])
def test_attn_layout_says_which_backward_the_layers_took(
        interpret, budget, words, monkeypatch, lowering_record):
    """Read from what the traced calls did, as the blockwise count is: the
    one kernel where a key-value head's ``dk`` and ``dv`` fit VMEM, else
    the ``dq`` and the ``dk``/``dv`` kernel and why."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret)
    if budget:
        monkeypatch.setattr(sparse_lm.kernels, "VMEM_LIMIT_BYTES", budget)
    cfg = SparseLMConfig(**dict(TINY, head_dim=128))
    fam.trace(cfg)
    layout = sparse_lm.engagement_records(cfg)["attn_layout"]
    assert layout.startswith(f"blockwise 512: {4 * interpret} of 4 layers, "
                             "1 full no-rope + 3 window 8 rope, ")
    assert f"2 query heads a key-value head{words}, rotary (" in layout


@pytest.mark.parametrize("interpret, head_dim, text_len, words", [
    (True, 128, 16, "(one pass on the lanes: 3 of 3 rope layers)"),
    (True, 64, 16, "(XLA: head_dim 64 is not whole 128-lane tiles)"),
    (True, 128, 12, "(XLA: 28 rows are not whole sublane tiles of 8)"),
    (False, 128, 16, "(XLA: no Mosaic backend)"),
    (None, 128, 16, "(XLA: none traced)"),
])
def test_attn_layout_says_which_lowering_the_rotary_took(
        interpret, head_dim, text_len, words, monkeypatch, lowering_record):
    """Read from what the traced calls did, as the blockwise count is; a
    configuration with no head norms: the rotary is a pass of its own.
    (``None``: kernels there are, and nothing was traced.)"""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", interpret is not False)
    cfg = SparseLMConfig(**dict(TINY, head_dim=head_dim,
                                text_seq_len=text_len))
    if interpret is not None:
        fam.trace(cfg)
    layout = sparse_lm.engagement_records(cfg)["attn_layout"]
    assert layout.endswith(f", rotary {words}") and "normed" not in layout
    none = dataclasses.replace(cfg, layer_kinds=("full_nope",) * 4)
    assert "rotary" not in sparse_lm.engagement_records(none)["attn_layout"]


def test_the_rotary_in_its_pass_is_the_xla_lowering(monkeypatch,
                                                    lowering_record):
    """Loss and every gradient leaf of a tiny model of one rope layer (the
    kind's one shape of call: the pass is held to a layer's gradients, not
    to a stack of the same layer) that rotates queries and keys in the pass
    (interpreted; 32 tokens), against the same model with the rotary as
    ``apply_rotary_lanes``: the same f32 model to its rounding."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = SparseLMConfig(**dict(TINY, head_dim=128, text_seq_len=16,
                                num_hidden_layers=1,
                                layer_kinds=("window_rope",)))
    # the queries' 4 heads and the keys' 2
    took = lambda: {lowering_record.why_not(
        "rotary", (cfg.total_seq_len, heads * 128, 128)) for heads in (4, 2)}
    assert fam.a_pass_is_its_xla_lowering(
        cfg, took, lambda: monkeypatch.setattr(
            sparse_lm.head_norm, "fits", lambda *a: "the test says so"),
        moved=False) == ({None}, {"the test says so"})


def test_the_reference_at_the_sets_the_program_chose():
    """``loss_and_grads_at``: the reference routed by given sets. At the
    program's own (sown by every layer; in f32 they are the reference's)
    it is ``loss_and_grads``; at other sets it is another function, whose
    routing weights are the softmax of its own scores at those experts."""
    cfg = SparseLMConfig(**TINY)
    model = sparse_lm.build(cfg)
    params, (text, image) = fam.params(cfg, 2, moved=False), batch(cfg)
    _, kept = jax.jit(lambda *a: model.apply(
        *a, mutable=["intermediates"]))(params, text, image)
    ours = np.stack([np.asarray(kept["intermediates"][f"layer_{i}"]
                                ["chosen"][0]) for i in range(4)])
    assert ours.shape == (4, 2, 28, cfg.experts_per_token)
    theirs = np.asarray(Y.chosen_experts(params, text, image, as_file(cfg)))
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))
    own_loss, own = Y.loss_and_grads(params, text, image, as_file(cfg))
    at_loss, at = Y.loss_and_grads_at(ours, params, text, image, as_file(cfg))
    assert float(at_loss) == pytest.approx(float(own_loss), rel=1e-6)
    for g, r in zip(jax.tree.leaves(at), jax.tree.leaves(own)):
        assert rel_l2(g, r) < 1e-5
    other = (ours + 1) % cfg.num_experts
    other_loss, _ = Y.loss_and_grads_at(other, params, text, image,
                                        as_file(cfg))
    assert abs(float(other_loss) - float(own_loss)) > 1e-6
    a = jax.random.normal(jax.random.PRNGKey(4), (5, cfg.hidden_size))
    router = jax.random.normal(jax.random.PRNGKey(5), (cfg.hidden_size, 8))
    given = jnp.asarray([[7, 0]] * 5)
    idx, p = Y.route(a, router, 2, given)
    np.testing.assert_array_equal(idx, given)
    np.testing.assert_allclose(
        p, jax.nn.softmax((a @ router)[:, [7, 0]], -1), rtol=1e-6)


@pytest.mark.parametrize("kernels", [False, True])
def test_the_shares_add_up_to_the_uncut_expert_layer(kernels, monkeypatch):
    """8 experts over 4 shares of 2: the four partial results, summed,
    equal the reference's whole expert layer; what every share computes
    alike (the router here; attention likewise) is counted once, and every
    assignment is computed by exactly one share. With ``kernels`` the
    sorted lowering and its grouped Pallas products, interpreted; without,
    the dense lowering a backend with no Mosaic kernels takes."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", kernels)
    cfg = SparseLMConfig(**TINY)
    rng = jax.random.split(jax.random.PRNGKey(3), 6)
    n, d, f = 56, cfg.hidden_size, cfg.expert_width
    a = jax.random.normal(rng[0], (n, d))
    m = jax.random.normal(rng[1], (n, d))
    router = jax.random.normal(rng[2], (d, 8))
    whole = {"gate": jax.random.normal(rng[3], (8, d, f)) * 0.2,
             "up": jax.random.normal(rng[4], (8, d, f)) * 0.2,
             "down": jax.random.normal(rng[5], (8, f, d)) * 0.2}
    want = Y.whole_layer_experts(m, a, router, whole, cfg.experts_per_token)
    idx, p = Y.route(a, router, cfg.experts_per_token)   # computed once

    total, here = jnp.zeros((n, d)), 0.0
    for share in range(4):
        mine = {k: w[2 * share: 2 * share + 2] for k, w in whole.items()}
        y, computed = sparse_lm.held_experts(
            m, idx, p, mine["gate"], mine["up"], mine["down"],
            offset=2 * share, rows=n * 2)
        total, here = total + y, here + float(computed)
        # and the reference, given the same share, gives the same part
        np.testing.assert_allclose(
            y, Y.expert_sum(m, idx, p, mine, 2 * share), atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert here == n * cfg.experts_per_token


@pytest.mark.parametrize("rows_over_expected, fits", [(2.0, True),
                                                      (0.5, False)])
def test_no_token_is_dropped_when_the_router_sends_everything_to_one_expert(
        rows_over_expected, fits, monkeypatch):
    """A router forced onto one held expert sends it every token: twice
    what a uniform router sends to the two held experts together. The
    usual buffer (2 x expected) just holds that and the sorted lowering
    computes it; a smaller one does not, and the dense lowering takes the
    step. Either way every assignment to a held expert is computed,
    forward and backward."""
    monkeypatch.setattr(sparse_lm, "ROWS_OVER_EXPECTED", rows_over_expected)
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = SparseLMConfig(**dict(TINY, experts_held=2, expert_offset=4))
    n = 1024
    rows = sparse_lm.dispatch_rows(n, cfg)
    assert (rows >= n) == fits and rows < n * 2   # never the worst case
    rng = jax.random.split(jax.random.PRNGKey(5), 5)
    d, f = cfg.hidden_size, cfg.expert_width
    m = jax.random.normal(rng[0], (n, d))
    experts = {"gate": jax.random.normal(rng[1], (2, d, f)) * 0.2,
               "up": jax.random.normal(rng[2], (2, d, f)) * 0.2,
               "down": jax.random.normal(rng[3], (2, f, d)) * 0.2}
    # every token: expert 5 (held) first, expert 0 (elsewhere) second
    idx = jnp.tile(jnp.asarray([[5, 0]], jnp.int32), (n, 1))
    p = jax.nn.softmax(jax.random.normal(rng[4], (n, 2)), -1)

    def system(m, p, experts):
        y, computed = sparse_lm.held_experts(
            m, idx, p, experts["gate"], experts["up"], experts["down"],
            offset=4, rows=rows)
        return jnp.sum(y * jnp.cos(y)), computed

    def reference(m, p, experts):
        y = Y.expert_sum(m, idx, p, experts, 4)
        return jnp.sum(y * jnp.cos(y))

    (value, computed), grads = jax.jit(jax.value_and_grad(
        system, (0, 1, 2), has_aux=True))(m, p, experts)
    want, ref_grads = jax.value_and_grad(reference, (0, 1, 2))(m, p, experts)
    assert float(computed) == n             # every token, none dropped
    assert float(value) == pytest.approx(float(want), rel=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        assert rel_l2(g, r) < 2e-5
    # no row of the result is zero: every token went through expert 5
    y, _ = sparse_lm.held_experts(m, idx, p, experts["gate"], experts["up"],
                                  experts["down"], offset=4, rows=rows)
    assert float(jnp.min(jnp.linalg.norm(y, axis=1))) > 0


def _uniform(rng, n, k, experts):
    return np.argsort(rng.normal(size=(n, experts)), axis=1)[:, :k]


def _one_expert_a_tile(rng, n, k, experts):
    """Every token's first choice is held expert 3: each tile's run in
    its group is the whole tile."""
    idx = _uniform(rng, n, k, experts)
    return np.concatenate([np.full((n, 1), 3), np.where(
        idx[:, 1:] == 3, 7, idx[:, 1:])], axis=1)


def _a_tile_and_an_expert_with_none(rng, n, k, experts):
    """Held expert 4 is never chosen; tokens 256..511 choose only experts
    that live elsewhere (0, 1, 6, 7 of 8 with 2..5 held)."""
    idx = _uniform(rng, n, k, experts)
    idx = np.where(idx == 4, (idx + 1 + np.arange(k)) % experts, idx)
    idx[256:512] = np.asarray([0, 1, 6, 7])[_uniform(rng, 256, k, 4)]
    # the repair may have named an expert twice: a token names one once
    twice = np.asarray([len(set(row)) < k for row in idx])
    idx[twice] = np.asarray([2, 3, 5, 0])[:k]
    return idx


SUMS = {
    # name: (tokens, router, dtype, NaN in the rows no tile wrote)
    "uniform": (1024, _uniform, "float32", False),
    "a_whole_tile_on_one_expert": (512, _one_expert_a_tile, "float32",
                                   False),
    "an_expert_and_a_tile_with_none": (768, _a_tile_and_an_expert_with_none,
                                       "float32", False),
    "tokens_no_multiple_of_the_tile": (1000, _uniform, "float32", False),
    "unwritten_rows_hold_nan": (700, _uniform, "bfloat16", True),
}


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["dispatch", "combine"])
@pytest.mark.parametrize("case", list(SUMS))
def test_the_kernel_over_runs_against_one_gather_a_slot(case, weighted,
                                                        monkeypatch):
    """The token-major sums of the sorted lowering, on one plan by both
    lowerings: the Pallas kernel over the runs a token tile owns in every
    held expert's group (interpreted) and one gather of N rows a slot.
    Without weights (dispatch's backward) and with (combine's forward);
    only the order of a token's additions may differ."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    n, router, dtype, nan = SUMS[case]
    k, experts, held, offset, d = 2, 8, 4, 2, 64
    rng = np.random.default_rng(11)
    idx = jnp.asarray(router(rng, n, k, experts), jnp.int32)
    assert all(len(set(row)) == k for row in np.asarray(idx))
    p = jax.nn.softmax(jnp.asarray(rng.normal(size=(n, k)), jnp.float32), -1)
    plan = jax.jit(sparse_lm.dispatch_plan, static_argnums=(1, 2, 3))(
        idx, offset, held, n * k)
    rows = jnp.asarray(rng.normal(size=(plan.token.shape[0], d)), dtype)
    written = int(plan.written)
    assert written < rows.shape[0]       # some tile holds no group
    if nan:
        rows = rows.at[written:].set(jnp.nan)
    assert sparse_lm.runs_why_not(n, k, held, d, dtype) is None
    weight = p if weighted else None
    want = sparse_lm._sum_over_slots(rows, plan, weight)
    got = jax.jit(sparse_lm._sum_to_tokens)(rows, plan, weight)
    assert got.shape == (n, d) and got.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    if not weighted and dtype == "float32":
        np.testing.assert_array_equal(got, want)    # two addends: no order
    # what the cases are for
    runs = np.diff(np.asarray(plan.start), axis=0)
    spills = int(token_sum.spills(plan.start))
    if case == "a_whole_tile_on_one_expert":
        assert (runs[:, 1] == 256).all() and spills >= 2
        assert float(jnp.min(jnp.linalg.norm(got, axis=1))) > 0
    if case == "an_expert_and_a_tile_with_none":
        assert (runs[:, 2] == 0).all() and (runs[1] == 0).all()
        assert float(jnp.max(jnp.abs(got[256:512]))) == 0.0
    if case == "tokens_no_multiple_of_the_tile":
        assert plan.start.shape == (5, held)
    # and in the rows' own dtype, as dispatch's backward asks for it
    low = jax.jit(lambda *a: sparse_lm._sum_to_tokens(*a, jnp.bfloat16))(
        rows, plan, weight)
    np.testing.assert_array_equal(low, got.astype(jnp.bfloat16))


TO_ROWS = {
    # name: (tokens, router)
    "uniform": (1024, _uniform),
    "everything_on_one_expert": (512, _one_expert_a_tile),
    "an_expert_and_a_tile_with_none": (768, _a_tile_and_an_expert_with_none),
    "tokens_no_multiple_of_the_tile": (1000, _uniform),
    # sixteen token tiles: the runs' boundaries in the held experts' groups
    # fall on every residue of ALIGN
    "boundaries_at_every_residue": (4096, _uniform),
    "fewer_tokens_than_a_tile": (56, _uniform),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(TO_ROWS))
def test_tokens_go_to_rows_by_runs_as_by_one_gather(case, dtype,
                                                    monkeypatch):
    """Tokens to the dispatch buffer's rows, on one plan by both lowerings:
    the Pallas kernel over runs (``token_sum_kernels.rows_of``: the
    transpose of the token-major sum, interpreted) and the XLA gather
    ``_rows_of``, bit for bit in every row of an active row tile (a
    token's row where one holds an assignment, zero elsewhere). Rows of
    inactive tiles are not written (NaN, interpreted): every reader masks
    them."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    n, router = TO_ROWS[case]
    k, experts, held, offset, d = 2, 8, 4, 2, 64
    rng = np.random.default_rng(11)
    idx = jnp.asarray(router(rng, n, k, experts), jnp.int32)
    plan = jax.jit(sparse_lm.dispatch_plan, static_argnums=(1, 2, 3))(
        idx, offset, held, n * k)
    source = jnp.asarray(rng.normal(size=(n, d)), dtype)
    assert sparse_lm.rows_why_not(n, plan.token.shape[0], held, d,
                                  dtype) is None
    want = sparse_lm._rows_of(source, plan)
    got = jax.jit(lambda s, plan: sparse_lm._to_rows(s, plan, "tokens"))(
        source, plan)
    assert got.shape == want.shape and got.dtype == want.dtype
    written = int(plan.written)
    assert 0 < written < want.shape[0]       # some tile holds no group
    np.testing.assert_array_equal(got[:written].astype(jnp.float32),
                                  want[:written].astype(jnp.float32))
    assert float(jnp.max(jnp.abs(want[written:].astype(jnp.float32)))) == 0
    # and the inverse movement gives every token back once an assignment
    back = sparse_lm._sum_to_tokens(got, plan)
    np.testing.assert_array_equal(
        back, source.astype(jnp.float32)
        * jnp.sum(plan.here, axis=1, keepdims=True))
    # what the cases are for
    start = np.asarray(plan.start)
    runs, spills = np.diff(start, axis=0), int(token_sum.spills(plan.start))
    if case == "everything_on_one_expert":
        assert (runs[:, 1] == 256).all() and spills >= 2
    if case == "an_expert_and_a_tile_with_none":
        assert (runs[:, 2] == 0).all() and (runs[1] == 0).all()
        # the expert with no row owns one row tile, all of it zero
        first = int(start[0, 2])
        assert first + 256 <= written
        assert float(jnp.max(jnp.abs(got[first:first + 256]))) == 0.0
    if case == "tokens_no_multiple_of_the_tile":
        assert plan.start.shape == (5, held)
    if case == "boundaries_at_every_residue":
        assert set((start % token_sum.ALIGN).reshape(-1)) == set(
            range(token_sum.ALIGN))
    if case == "fewer_tokens_than_a_tile":
        assert plan.start.shape == (2, held)


def _held_experts_cotangents(m, idx, p, experts, rows, rounded_to,
                             act="silu"):
    """Value and the five cotangents of a loss that rounds the layer's
    result to ``m``'s dtype first, as ``ExpertLayer`` does."""
    def loss(m, p, experts):
        y, _ = sparse_lm.held_experts(
            m, idx, p, experts["gate"], experts["up"], experts["down"],
            offset=2, rows=rows, act=act, rounded_to=rounded_to)
        y = y.astype(m.dtype).astype(jnp.float32)
        return jnp.sum(y * jnp.cos(y))
    return jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(m, p, experts)


def _experts_operands(router, dtype, n=512, k=2, held=4, d=64, f=32):
    rng = np.random.default_rng(5)
    idx = jnp.asarray(router(rng, n, k, 8), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    m = jax.random.normal(keys[0], (n, d)).astype(dtype)
    p = jax.nn.softmax(jax.random.normal(keys[1], (n, k)), -1)
    experts = {name: (jax.random.normal(key, shape) * 0.2).astype(dtype)
               for name, key, shape in (("gate", keys[2], (held, d, f)),
                                        ("up", keys[3], (held, d, f)),
                                        ("down", keys[4], (held, f, d)))}
    return m, idx, p, experts


@pytest.mark.parametrize("router", [_uniform, _one_expert_a_tile],
                         ids=["uniform", "spills"])
def test_the_cotangent_moves_in_the_dtype_the_caller_rounds_to(
        router, monkeypatch, lowering_record):
    """``held_experts``' five cotangents three ways, equal to the last bit:
    the kernel over runs with the cotangent moved as bfloat16 (the caller
    states what it rounds the result to), the kernel with the cotangent in
    f32 (a caller that states nothing), and one XLA gather each. Which
    movement ran is read from the lowering record."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    m, idx, p, experts = _experts_operands(router, jnp.bfloat16)
    (n, k), (held, d, _) = idx.shape, experts["gate"].shape
    key = lambda dtype: sparse_lm._rows_key(held, d, dtype, "cotangent")
    said = lambda dtype: lowering_record.recorded(sparse_lm.ROWS_SITE,
                                                  key(dtype))
    stated = _held_experts_cotangents(m, idx, p, experts, n * k, m.dtype)
    assert said("bfloat16") == {"why_not": None, "stated": True}
    assert said("float32") is None           # no f32 movement was traced
    silent = _held_experts_cotangents(m, idx, p, experts, n * k, None)
    assert said("float32") == {"why_not": None, "stated": False}
    monkeypatch.setattr(sparse_lm, "ROWS_NS_A_KIB", {2: 1e9, 4: 1e9})
    gathered = _held_experts_cotangents(m, idx, p, experts, n * k, m.dtype)
    assert "cost more than a gather" in said("bfloat16")["why_not"]
    assert "cost more than a gather" in lowering_record.recorded(
        sparse_lm.ROWS_SITE,
        sparse_lm._rows_key(held, d, "bfloat16", "tokens"))["why_not"]
    leaves = lambda out: [np.asarray(a.astype(jnp.float32))
                          for a in jax.tree.leaves(out)]
    assert len(leaves(stated)) == 6          # the value, dm, dp, three dw
    for a, b, c in zip(*map(leaves, (stated, silent, gathered))):
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("router", [_uniform,
                                    _a_tile_and_an_expert_with_none],
                         ids=["uniform", "an_expert_with_none"])
def test_the_expert_block_on_the_tile_is_the_three_products_a_direction(
        router, act, dtype, monkeypatch, lowering_record):
    """``held_experts``' value and five cotangents by both forms of the
    expert block, interpreted: gate, up and the activation one kernel, the
    cotangents on the tile and one ``dxs`` (where two weight blocks and the
    tiles fit VMEM), and the three products a direction with XLA code
    between them (where they do not: the limit shrunk). Equal to the last
    bit, but for the one rounding the block removes: ``dm`` in a 16-bit
    dtype, whose two ``dxs`` are summed in f32 and rounded once."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    m, idx, p, experts = _experts_operands(router, dtype)
    (n, k), (_, d, f) = idx.shape, experts["gate"].shape
    said = lambda: lowering_record.recorded(
        sparse_lm.PRODUCTS_SITE, sparse_lm._block_key(d, f, dtype))
    on_the_tile = _held_experts_cotangents(m, idx, p, experts, n * k,
                                           m.dtype, act)
    assert said() == {"why_not": None}
    monkeypatch.setattr(grouped, "_VMEM", 64 * 1024)
    three = _held_experts_cotangents(m, idx, p, experts, n * k, m.dtype, act)
    assert said()["why_not"].startswith(
        f"two blocks of {d} x {f} and the tiles need ")
    leaves = lambda out: [np.asarray(a.astype(jnp.float32))
                          for a in jax.tree.leaves(out)]
    names = ["value", "dm", "dp", "ddown", "dgate", "dup"]
    for name, a, b in zip(names, leaves(on_the_tile), leaves(three),
                          strict=True):
        assert np.isfinite(a).all() and np.abs(a).max() > 0, name
        if name == "dm" and dtype == "bfloat16":
            # a rounding removed: within the roundings of two bf16
            # addends of the largest size, and not equal everywhere
            np.testing.assert_allclose(a, b, rtol=0,
                                       atol=2.0 ** -7 * np.abs(b).max())
            assert (a != b).any()
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_moe_layout_says_which_form_the_expert_block_took_and_why_not(
        monkeypatch, lowering_record):
    """The ``setup/warmup`` row's ``moe_layout`` ends with the form the
    last traced call of the configuration's experts took; the log line of
    the site says it too."""
    cfg = SparseLMConfig(**dict(TINY, dtype="bfloat16"))
    layout = lambda: sparse_lm.engagement_records(cfg)["moe_layout"]
    assert "expert block" not in layout()
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    m, idx, p, experts = _experts_operands(_uniform, "bfloat16", n=56)
    run = lambda: sparse_lm.held_experts(
        m, idx, p, experts["gate"], experts["up"], experts["down"],
        offset=2, rows=112)
    run()
    assert layout().endswith(
        "; expert block: gate, up and activation one kernel; cotangents on "
        "the tile; one dxs; inactive tiles unmoved")
    monkeypatch.setattr(grouped, "_VMEM", 64 * 1024)
    run()
    assert layout().endswith(
        "; expert block: three products a direction (two blocks of 64 x 32 "
        "and the tiles need 0.4 MiB of VMEM, over 0.0625)")
    # experts of another size said nothing of this configuration's
    other = SparseLMConfig(**dict(TINY, dtype="bfloat16", expert_width=64))
    assert "expert block" not in sparse_lm.engagement_records(
        other)["moe_layout"]


def test_the_share_of_row_tiles_that_hold_rows_on_a_known_plan(
        monkeypatch, lowering_record):
    """``moe_tiles_active_pct`` from the layer's counter: 1 024 tokens, top
    2 of 8 with experts 2..5 held and a router that sends every token to
    expert 3 first: its 1 024 rows are 4 tiles, the other three held
    experts' 150-odd rows a tile each, of a grid of 2 048 / 256 + 4 = 12:
    7 of 12. A call whose assignments pass the buffer is dense: 0."""
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    cfg = SparseLMConfig(**TINY)
    layer = sparse_lm.ExpertLayer(cfg)
    rng = np.random.default_rng(11)
    m = jnp.asarray(rng.normal(size=(1, 1024, cfg.hidden_size)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), m, jnp.zeros(
        (1, 1024, 2), jnp.int32), jnp.ones((1, 1024, 2)) / 2)
    p = jnp.ones((1, 1024, 2)) / 2
    idx = jnp.asarray(_one_expert_a_tile(rng, 1024, 2, 8), jnp.int32)[None]
    _, counters = layer.apply(params, m, idx, p)
    assert sparse_lm.dispatch_rows(1024, cfg) == 2048
    assert float(counters["dense"]) == 0.0
    assert float(counters["tiles_active"]) == pytest.approx(7 / 12)
    # every token on two held experts: 4 + 4 tiles and the two empty
    # experts' one each
    idx = jnp.tile(jnp.asarray([[3, 4]], jnp.int32), (1, 1024, 1))
    _, counters = layer.apply(params, m, idx, p)
    assert float(counters["tiles_active"]) == pytest.approx(10 / 12)
    monkeypatch.setattr(sparse_lm, "ROWS_OVER_EXPECTED", 0.5)
    _, counters = layer.apply(params, m, idx, p)
    assert float(counters["dense"]) == 1.0
    assert float(counters["tiles_active"]) == 0.0


def test_a_weights_pieces_add_up_to_it_exactly():
    """Three bf16 numbers for an f32 routing weight (under jit, where a
    rounding that is converted back may be dropped); the weight itself for
    f32 rows."""
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (64, 6))
                       * 4, -1)
    w = jnp.concatenate([w, jnp.asarray([[1.0, 0.0, 1e-30, 1 / 3, 0.999999,
                                          2.0 ** -100]])])
    pieces = jax.jit(lambda w: token_sum.weight_pieces(w, jnp.bfloat16))(w)
    assert pieces.shape == (65, 18) and pieces.dtype == jnp.float32
    np.testing.assert_array_equal(
        pieces.astype(jnp.bfloat16).astype(jnp.float32), pieces)
    np.testing.assert_array_equal(
        pieces[:, :6] + pieces[:, 6:12] + pieces[:, 12:], w)
    assert token_sum.weight_pieces(w, jnp.float32) is w


def test_which_lowering_a_token_major_sum_takes_is_read_off_its_shapes(
        monkeypatch, lowering_record):
    """The kernel over runs while the held experts stay under about ten a
    slot and their windows fit VMEM; one gather a slot otherwise. The
    cell's layout takes the kernel; the crossing lies past what fits."""
    why_not = sparse_lm.runs_why_not
    assert why_not(16384, 6, 8, 2560, "bfloat16") is None     # the cell
    assert why_not(16384, 6, 16, 2560, "bfloat16") is None
    assert "VMEM" in why_not(16384, 6, 32, 2560, "bfloat16")
    assert "VMEM" in why_not(16384, 6, 64, 2560, "bfloat16")  # all held
    assert why_not(16384, 6, 64, 256, "bfloat16") == (
        "4096 windows cost more than 6 gathers of 16384 rows")
    assert why_not(16384, 6, 60, 256, "bfloat16") is None     # the crossing
    assert why_not(16384, 1, 16, 2560, "bfloat16") == (
        "1024 windows cost more than 1 gathers of 16384 rows")
    assert why_not(16384, 1, 8, 2560, "bfloat16") is None
    assert "lane tiles" in why_not(16384, 6, 7, 2560, "bfloat16")
    assert why_not(16384, 6, 8, 2560, "float16") == "rows in float16"
    # the setup/warmup row says what the last traced sum of the
    # configuration's shapes took
    cfg = SparseLMConfig(**dict(TINY, hidden_size=192, dtype="bfloat16"))
    layout = lambda: sparse_lm.engagement_records(cfg)["moe_layout"]
    assert layout().endswith("token-major sums: none traced (the dense "
                             "lowering)")
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    idx = jnp.asarray(np.argsort(np.random.default_rng(0).normal(
        size=(56, 8)), axis=1)[:, :2], jnp.int32)
    plan = sparse_lm.dispatch_plan(idx, 2, 4, 112)
    rows = jnp.ones((plan.token.shape[0], 192), jnp.bfloat16)
    sparse_lm._sum_to_tokens(rows, plan)
    assert layout().endswith("one device; token-major sums: runs of rows, "
                             "56 tokens a tile, windows of 64 rows")
    monkeypatch.setattr(sparse_lm, "WINDOW_NS", 1e6)
    sparse_lm._sum_to_tokens(rows, plan)
    assert layout().endswith("token-major sums: one gather a slot (4 windows "
                             "cost more than 2 gathers of 56 rows)")

def test_which_lowering_takes_tokens_to_rows_is_read_off_its_shapes(
        monkeypatch, lowering_record):
    """The kernel over runs where the buffer has more rows than about 0.6
    of the windows' (1.1 in f32) and the windows fit VMEM; one gather
    otherwise. Of the four cells' layouts three take the kernel, the one
    with the fewest rows keeps its gather; the ``setup/warmup`` row says
    which, and in which dtype the cotangent moved."""
    why_not = sparse_lm.rows_why_not
    assert why_not(16384, 26624, 8, 2560, "bfloat16") is None  # smallthinker
    assert why_not(8192, 18432, 8, 2048, "bfloat16") is None   # lfm2moe
    assert why_not(8192, 10240, 8, 2048, "bfloat16") is None   # trinitymini
    assert why_not(8192, 6144, 8, 2048, "bfloat16") == (       # joyaiflash
        "256 windows cost more than a gather of 6144 rows")
    assert why_not(8192, 18432, 8, 2048, "float32") is None
    assert why_not(16384, 26624, 8, 2560, "float32") == (
        "512 windows cost more than a gather of 26624 rows")
    assert "VMEM" in why_not(16384, 26624, 32, 2560, "bfloat16")
    assert "lane tiles" in why_not(16384, 26624, 7, 2560, "bfloat16")
    assert why_not(16384, 26624, 8, 2560, "float16") == "rows in float16"
    cfg = SparseLMConfig(**dict(TINY, hidden_size=192, dtype="bfloat16"))
    layout = lambda: sparse_lm.engagement_records(cfg)["moe_layout"]
    assert layout().endswith("token-major sums: none traced (the dense "
                             "lowering)")
    monkeypatch.setattr(attention, "_PALLAS_INTERPRET", True)
    idx = jnp.asarray(np.argsort(np.random.default_rng(0).normal(
        size=(56, 8)), axis=1)[:, :2], jnp.int32)
    plan = sparse_lm.dispatch_plan(idx, 2, 4, 112)
    tokens = jnp.ones((56, 192), jnp.bfloat16)
    sparse_lm._sum_to_tokens(sparse_lm._to_rows(tokens, plan, "tokens"),
                             plan)
    assert layout().endswith(
        "windows of 64 rows; rows from tokens: kernel over runs")
    sparse_lm._to_rows(tokens.astype(jnp.float32), plan, "cotangent",
                       stated=False)
    assert layout().endswith(
        "rows from tokens: kernel over runs; cotangent gathered as float32 "
        "(the caller states no rounding), kernel over runs")
    monkeypatch.setattr(sparse_lm, "ROWS_NS_A_KIB", {2: 100.0, 4: 100.0})
    sparse_lm._to_rows(tokens, plan, "tokens")
    sparse_lm._to_rows(tokens, plan, "cotangent", stated=True)
    assert layout().endswith(
        "rows from tokens: one gather (4 windows cost more than a gather "
        "of 1280 rows); cotangent gathered as bfloat16 (the caller rounds "
        "the result to it), one gather (4 windows cost more than a gather "
        "of 1280 rows)")



def test_what_a_configuration_states_is_a_field_and_not_a_flag():
    """The four facts of the source that ``validate`` holds to one value,
    and the embedding's scale at init, are fields (the configuration file
    states them) and no entry point's flags; layers are always
    rematerialised: no field for it."""
    parser = run_trainer.build_parser()
    flags = {action.dest for action in parser._actions}
    fields = {f.name for f in dataclasses.fields(SparseLMConfig)}
    stated = set(SparseLMConfig.no_flag)
    assert stated == {"router_softmax_over_chosen", "tied_embeddings",
                      "router_input", "attention_bias",
                      "embed_init_std"} <= fields
    assert flags & stated == {"tied_embeddings"}     # the DALL-E's own flag
    assert "remat" not in fields
    with pytest.raises(SystemExit):
        parser.parse_args(["--preset", "smallthinker21b",
                           "--router-input", "layer_input"])


def test_the_dalle_keeps_its_records_and_its_step_rows():
    from dalle_tpu.config import tiny_model_config
    from dalle_tpu.models import dalle
    cfg = tiny_model_config()
    assert family(cfg) is dalle and dalle.step_attributes(cfg) == ()
    records = dalle.engagement_records(cfg)
    assert set(records) == {"layer_loop", "attn_layout"}
    assert cfg.optimizer_stacking() == {"stacked_reps": 0,
                                        "stacked_experts": 0}
    assert len(dataclasses.fields(ModelConfig)) == 29
