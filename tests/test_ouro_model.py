"""``OuroLMConfig`` (preset ``ouro2b6``) through models/sparse_lm.py at a
tiny size, seeded random weights, f32: the family's cases over its row
(tests/sparse_family.py), and what only it has: the passes as one traced
body against the passes unrolled (tests/ouro_unrolled.py), planted faults
that the reference must tell, a shared leaf's gradient as the sum over the
passes, and the streamed head's rows."""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ouro_unrolled
import sparse_family as fam
from benchmark.manifest import Manifest
from dalle_tpu.config import (AfmoeLMConfig, OuroLMConfig, SparseLMConfig,
                              ouro2b6_model_config)
from dalle_tpu.models import decode, sparse_lm
from sparse_family import rel_l2

Y = Manifest().yardstick("ouro")

# two layers run three times; a sequence (28) of two fields
TINY = dict(hidden_size=32, num_hidden_layers=2, num_dense_layers=2,
            num_heads=2, num_kv_heads=2, head_dim=16, dense_width=48,
            vocab_size=64, text_seq_len=12, image_grid=4, vocab_text=32,
            vocab_image=32, dtype="float32", head_chunk=16, total_ut_steps=3)
# the widths the kernels take (interpreted): heads of one lane tile, a hidden
# size of one, a sequence of whole sublane tiles (64); one layer, two passes
KERNEL_WIDTHS = dict(head_dim=128, num_heads=1, num_kv_heads=1,
                     hidden_size=128, dense_width=128, text_seq_len=48,
                     num_hidden_layers=1, num_dense_layers=1,
                     total_ut_steps=2)


def exits_with(stacks, p, text, image, model, carry_normed=True):
    """The yardstick's loop over the passes, written out once more so that
    a test can plant what ``Y.exits`` has no argument for: pass t runs the
    leaves ``stacks[t]``, and with ``carry_normed`` false the raw stream
    goes round (the exit is normed all the same)."""
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    x = p["token_emb"][ids]
    lams, nll = [], []
    for sp in stacks:
        for i in range(model["num_hidden_layers"]):
            x = Y.layer(sp[f"layer_{i}"], x, model)
        z = Y._rms_norm(x, sp["final_norm"], model["rms_eps"])
        lams.append(Y.exit_gate(z, p)[:, :-1])
        nll.append(Y.exit_nll(z, p["lm_head"], ids))
        x = z if carry_normed else x
    return Y.exit_distribution(lams), jnp.stack(nll)


def _patched(name, make):
    """The yardstick's function ``name`` replaced by ``make(plain)``."""
    def patch(monkeypatch, model):
        monkeypatch.setattr(Y, name, make(getattr(Y, name)))
        return model
    return patch


def _raw_stream_goes_round(plain):
    return lambda params, text, image, model, checkpoint_blocks=False: \
        exits_with([params["params"]["passes"]] * model["total_ut_steps"],
                   params["params"], text, image, model, carry_normed=False)


def _no_post_attention_norm(plain):
    def layer(p, x, model):
        eps = model["rms_eps"]
        h = x + Y.attention(Y._rms_norm(x, p["attn_norm"], eps), p["attn"],
                            model)
        m = Y._rms_norm(h, p["ff_norm"], eps)
        return h + Y._rms_norm(Y.gated_block(m, p["ff"]["dense"]),
                               p["post_ff_norm"], eps)
    return layer


def _every_gate_read(plain):
    """``p_R = lam_R prod (1 - lam_j)``: the last gate read like the rest,
    so the distribution no longer sums to one."""
    def dist(lams):
        left, out = jnp.ones_like(lams[0]), []
        for lam in lams:
            out.append(lam * left)
            left = left * (1.0 - lam)
        return jnp.stack(out)
    return dist


def _head_from_the_last_exit_only(plain):
    """The head's gradient from the last exit's rows alone: the loss is the
    reference's, ``dW`` is not."""
    calls = itertools.count(1)

    def nll(z, head, ids):
        last = next(calls) % TINY["total_ut_steps"] == 0
        return plain(z, head if last else jax.lax.stop_gradient(head), ids)
    return nll


# what the reference is when a fault is planted in it (a key of ``model``
# where it has one, else a patch of the yardstick's module), and what has to
# move: the loss, or a gradient leaf where the loss cannot show it
FAULTS = {
    "a pass fewer": (dict(total_ut_steps=TINY["total_ut_steps"] - 1), "loss"),
    "no norm between the passes (the raw stream goes round)": (
        _patched("exits", _raw_stream_goes_round), "loss"),
    "the gate without its bias": (_patched(
        "exit_gate", lambda plain: lambda z, p: plain(
            z, dict(p, exit_gate_bias=0.0 * p["exit_gate_bias"]))), "loss"),
    "p_R made with lam_R": (
        _patched("exit_distribution", _every_gate_read), "loss"),
    "the entropy term dropped": (dict(exit_entropy_weight=0.0), "loss"),
    "a sandwich norm dropped": (
        _patched("layer", _no_post_attention_norm), "loss"),
    "the head's dW from the last exit only": (
        _patched("exit_nll", _head_from_the_last_exit_only),
        "['params']['lm_head']"),
}


@pytest.fixture(scope="module")
def whole():
    """The tiny model once: the configuration, weights whose every leaf
    counts (the gate's bias and the norms' scales moved off their initial
    values), a batch, the system's loss, aux and gradients."""
    cfg = OuroLMConfig(**TINY)
    cfg.validate()
    weights, (text, image) = fam.params(cfg), fam.batch(cfg)
    assert all(np.asarray(leaf).any() for leaf in jax.tree.leaves(weights))
    (loss, aux), grads = fam.system(cfg, weights, text, image)
    return cfg, weights, text, image, float(loss), aux, grads


class TestOuro2b6(fam.Family):
    config, preset = OuroLMConfig, "ouro2b6"
    preset_config, Y = staticmethod(ouro2b6_model_config), Y
    TINY, KERNEL_WIDTHS, EXPERT_LAYERS = TINY, KERNEL_WIDTHS, 0
    BLOCKWISE = {"full_rope": (None, 256)}
    ADDED = {"total_ut_steps", "pass_input", "exit_gate_input",
             "exit_gate_bias", "exit_gate_init_std", "exit_loss",
             "exit_entropy_weight"}
    # the parents' class attributes say "run once, no entropy term"
    NOT_NOUGHT_ELSEWHERE = ("total_ut_steps",)
    # every width and the passes are the source's
    PUBLISHED = dict(
        hidden_size=2048, num_heads=16, num_kv_heads=16, head_dim=128,
        dense_width=5632, total_ut_steps=4, rms_eps=1e-6, rope_theta=1e6,
        hidden_act="silu", vocab_size=24576, num_hidden_layers=6,
        num_dense_layers=6, num_experts=0, has_expert_layers=False,
        sandwich_norms=True, tied_embeddings=False, exit_entropy_weight=0.1)
    REFUSAL = ("looped stack", "a pass and layer")

    def the_yardstick_also(self, *, cfg, tree, aux, grads, said,
                           with_kernels, lowering_record, shut, **_):
        layers, passes = cfg.num_hidden_layers, cfg.total_ut_steps
        assert set(tree) == {"token_emb", "lm_head", "passes", "exit_gate",
                             "exit_gate_bias"}
        assert set(tree["passes"]) == {"final_norm", *(
            f"layer_{i}" for i in range(layers))}
        assert set(tree["passes"]["layer_0"]) == {
            "attn_norm", "attn", "post_attn_norm", "ff_norm", "ff",
            "post_ff_norm"}
        assert set(tree["passes"]["layer_0"]["attn"]) == {"q", "k", "v",
                                                          "out"}
        assert set(tree["passes"]["layer_0"]["ff"]) == {"dense"}
        assert tree["exit_gate"].shape == (cfg.hidden_size,)
        assert tree["exit_gate_bias"].shape == (1,)
        # every leaf has a gradient: the gate's two and the norms' too
        for name, leaf in fam.leaves(grads).items():
            assert float(jnp.abs(leaf).max()) > 0, name
        # the loss's parts add up, the exits are told apart, the
        # distribution is one
        assert float(aux["loss"]) == pytest.approx(
            float(aux["loss_main"]) + float(aux["loss_entropy"]), rel=1e-6)
        assert float(aux["loss_entropy"]) == pytest.approx(
            -cfg.exit_entropy_weight * float(aux["exit_entropy"]), rel=1e-6)
        exits = [float(aux[f"loss_exit_{i + 1}"]) for i in range(passes)]
        assert len(set(exits)) == passes
        assert 1.0 < float(aux["exit_expected_pass"]) < passes
        assert 0.0 < float(aux["exit_entropy"]) < np.log(passes)
        assert f"loss_exit_{passes + 1}" not in aux
        assert sparse_lm.step_attributes(cfg) == (
            "loss_main", "loss_entropy", *(
                f"loss_exit_{i + 1}" for i in range(passes)),
            "exit_expected_pass", "exit_entropy")
        # what the records say: one traced pass, the exits' rows in one
        # call of the head, no expert layer anywhere
        assert said["loop_layout"] == (
            f"{layers} layers x {passes} passes: {layers * passes} "
            f"applications of {layers} parameter sets, one traced pass")
        assert said["head_layout"].startswith(
            f"gradients made with the loss: 1 of 1 calls (main: the "
            f"{passes} exits' rows in one call under their exit weights")
        assert said["layer_loop"].startswith(f"a pass unrolled: {layers} ")
        assert "moe_layout" not in said
        rotary = "rotary", (cfg.total_seq_len, cfg.num_heads * cfg.head_dim,
                            cfg.head_dim)
        assert lowering_record.why_not(*rotary) == shut
        if with_kernels:
            assert said["attn_layout"].startswith(
                "blockwise 512: 1 of 1 layers, 1 full rope, 1 query heads a "
                "key-value head, backward: one kernel a tile (1 of 1 "
                "layers), rotary (one pass on the lanes: 1 of 1 rope layers)")

    def the_normal_path_also(self, *, names, warm, steps, **_):
        assert sum("['passes']" in name for name in names) == 2 * 11 + 1
        assert sum("exit_gate" in name for name in names) == 2
        assert warm["loop_layout"] == (
            "2 layers x 3 passes: 6 applications of 2 parameter sets, one "
            "traced pass")
        assert "moe_layout" not in warm and "mtp_layout" not in warm
        for row in steps:
            assert row["loss_exit_1"] != row["loss_exit_3"]
            assert 1.0 < row["exit_expected_pass"] < 3.0
            assert row["loss_entropy"] < 0.0 < row["exit_entropy"]

    def the_class_also(self, cfg, flags):
        for parent in (SparseLMConfig, AfmoeLMConfig):
            assert parent.total_ut_steps == 1
            assert parent().has_expert_layers
        assert {"total_ut_steps", "exit_entropy_weight"} <= flags
        assert not {"exit_loss", "pass_input", "exit_gate_input",
                    "exit_gate_bias", "exit_gate_init_std"} & flags
        replace = dataclasses.replace
        for wrong in (dict(total_ut_steps=1), dict(num_dense_layers=7),
                      dict(num_experts=8), dict(sandwich_norms=False),
                      dict(exit_gate_bias=False), dict(pass_input="stream"),
                      dict(exit_loss="last_exit"), dict(qk_norm=True),
                      dict(exit_entropy_weight=-0.1)):
            with pytest.raises(ValueError):
                replace(cfg, **wrong).validate()
        # the parent still refuses a stack with no expert layer
        with pytest.raises(ValueError, match="must leave an expert layer"):
            replace(AfmoeLMConfig(), num_dense_layers=5).validate()


def test_the_unrolled_passes_are_the_one_traced_pass(whole, monkeypatch,
                                                     lowering_record):
    """The passes as a Python loop over the same module (the lowering that
    exists in the tests only): the same leaves, the reference's loss and
    gradients, the scanned form's to rounding; the record says which form a
    trace took."""
    cfg, weights, text, image, loss, _, grads = whole
    monkeypatch.setattr(sparse_lm, "run_passes", ouro_unrolled.run_passes)
    model = sparse_lm.build(cfg)
    shapes = jax.eval_shape(lambda: sparse_lm.init_params(
        model, jax.random.PRNGKey(1)))
    assert jax.tree.structure(shapes) == jax.tree.structure(weights)
    (un_loss, _), un_grads = fam.program(cfg)(weights, text, image)
    assert sparse_lm.engagement_records(cfg)["loop_layout"].endswith(
        "unrolled: 3 traced passes")
    ref_loss, ref_grads = Y.loss_and_grads(weights, text, image,
                                           fam.as_file(cfg))
    for got in (loss, float(un_loss)):
        assert got == pytest.approx(float(ref_loss), rel=2e-6)
    fam.leaves_within(un_grads, ref_grads, 2e-5)
    fam.leaves_within(un_grads, grads, 2e-5)


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_is_told(whole, fault, monkeypatch):
    """The system agrees with the reference whole; with the fault planted
    in the reference it does not, by at least ten times the distance at
    which they agree: in the loss, or in the leaf the fault moves."""
    cfg, weights, text, image, loss, _, grads = whole
    patch, moves = FAULTS[fault]
    model = fam.as_file(cfg)
    model = (patch(monkeypatch, model) if callable(patch)
             else dict(model, **patch))
    if moves == "loss":
        faulty = fam.reference_loss(Y, weights, text, image, model)
        assert abs(faulty - loss) > 2e-5 * loss, fault
        return
    faulty_loss, faulty = Y.loss_and_grads(weights, text, image, model)
    assert float(faulty_loss) == pytest.approx(loss, rel=2e-6)
    ours, theirs = fam.leaves(grads), fam.leaves(faulty)
    assert rel_l2(ours[moves], theirs[moves]) > 2e-4, fault
    assert all(rel_l2(ours[name], theirs[name]) < 2e-5
               for name in ours if name != moves)


def test_a_shared_leafs_gradient_is_the_sum_over_the_passes(whole):
    """The stack's leaves unshared, a copy a pass: the system's gradient on
    a leaf is the sum of the copies' gradients, and no copy's alone."""
    cfg, weights, text, image, loss, _, grads = whole
    model, p = fam.as_file(cfg), weights["params"]

    def loss_of(stacks):
        dist, nll = exits_with(stacks, p, text, image, model)
        entropy = -jnp.sum(dist * jnp.log(dist), 0)
        return (jnp.sum(dist * nll, 0)
                - model["exit_entropy_weight"] * entropy).mean()

    copies = [p["passes"]] * cfg.total_ut_steps
    unshared_loss, per_pass = jax.jit(jax.value_and_grad(loss_of))(copies)
    assert float(unshared_loss) == pytest.approx(loss, rel=2e-6)
    summed = jax.tree.map(lambda *g: sum(g), *per_pass)
    fam.leaves_within(grads["params"]["passes"], summed, 2e-5)
    ours = fam.leaves(grads["params"]["passes"])
    for one in per_pass:
        assert all(rel_l2(ours[name], leaf) > 1e-2
                   for name, leaf in fam.leaves(one).items())


def test_the_streamed_head_hands_back_its_rows_and_the_weights_gradient():
    """``rows``: every row's loss as a value, and ``d total / d weights``
    equal to it, beside the ``dx`` and ``dW`` the scan makes; a derivative
    that reaches the rows is refused like one that reaches the sums."""
    rng = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(rng[0], (40, 16))
    kernel = jax.random.normal(rng[1], (16, 24)) * 0.3
    targets = jax.random.randint(rng[2], (40,), 0, 24)
    weights = jax.random.uniform(rng[3], (40, 1))

    def plain(h, kernel, weights):
        logp = jax.nn.log_softmax(h @ kernel, -1)
        nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return jnp.sum(nll * weights[:, 0]), nll

    def streamed(h, kernel, weights):
        total, _, nll = sparse_lm._streamed_nll(h, kernel, targets, weights,
                                                16, rows=True)
        return total, nll

    (want, want_rows), want_grads = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True))(h, kernel, weights)
    (got, rows), grads = jax.jit(jax.value_and_grad(
        streamed, argnums=(0, 1, 2), has_aux=True))(h, kernel, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(rows, want_rows, rtol=1e-5, atol=1e-6)
    for a, b in zip(grads, want_grads):
        assert rel_l2(a, b) < 1e-5
    np.testing.assert_allclose(grads[2][:, 0], want_rows, rtol=1e-5,
                               atol=1e-6)
    with pytest.raises(TypeError, match="reported and not differentiated"):
        jax.grad(lambda h: jnp.sum(streamed(h, kernel, weights)[1]))(h)


def test_the_exit_distribution_is_one_whatever_the_gates():
    """``exit_log_probs`` against the reference's products, gates that
    saturate among them: the distribution sums to one and ``p ln p`` stays
    finite."""
    logit = jnp.asarray([[0.3, -40.0, 50.0, 2.0], [-1.0, 60.0, -50.0, 0.0],
                         [0.5, 0.1, 0.2, -90.0]])
    log_p = sparse_lm.exit_log_probs(logit)
    p = jnp.exp(log_p)
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, rtol=1e-6)
    want = Y.exit_distribution(list(jax.nn.sigmoid(logit)))
    np.testing.assert_allclose(p, want, rtol=1e-5, atol=1e-12)
    assert np.isfinite(np.asarray(p * log_p)).all()


def test_decode_refuses_a_looped_stack_by_its_mechanism():
    decode.refuse_looped_stack(SparseLMConfig())            # run once
    with pytest.raises(NotImplementedError, match="one a pass and layer"):
        decode.refuse_looped_stack(OuroLMConfig())
    with pytest.raises(NotImplementedError, match="4 times on one set"):
        decode.init_cache(OuroLMConfig(), batch=1)
