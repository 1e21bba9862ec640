"""``sparse_lm._streamed_nll``, the sparse family's streamed head: a
``jax.custom_vjp`` whose forward rule makes the gradients with the loss.
Against ``jax.grad`` of the plain unstreamed formula in f32, against the
parent's checkpointed scan body (copied here as it stood) in bf16, the
refusal of a derivative that reaches the reported sums, the jaxprs' count of
products, and one tiny model with a prediction module, whose head leaf gets
the sum of both uses' gradients."""
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import JoyAILMConfig
from dalle_tpu.models import sparse_lm
from sparse_family import rel_l2

# eight chunks, and arrays large enough that the ratio of two bf16 errors
# is its expectation to a few per cent
ROWS, HIDDEN, VOCAB, CHUNK = 512, 128, 512, 64


def parent_sums(h, kernel, targets, weights, chunk, tied=False):
    """The parent's ``_streamed_nll``: a checkpointed scan body, which the
    backward pass replays (the logits are multiplied twice)."""
    n = h.shape[0]
    pad = -n % chunk
    if pad:
        h, targets, weights = (jnp.pad(x, ((0, pad),) + ((0, 0),)
                                       * (x.ndim - 1))
                               for x in (h, targets, weights))

    @jax.checkpoint
    def body(sums, xs):
        hc, tc, wc = xs
        logits = jax.lax.dot_general(
            hc, kernel, (((1,), (1 if tied else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, tc[:, None], axis=-1)[:, 0]
        return sums + jnp.sum(nll[:, None] * wc, axis=0), None

    split = lambda x: x.reshape(-1, chunk, *x.shape[1:])
    sums, _ = jax.lax.scan(body, jnp.zeros(weights.shape[1:], jnp.float32),
                           (split(h), split(targets), split(weights)))
    return sums


def plain_sums(h, kernel, targets, weights, tied):
    """The formula, unstreamed, in f32 whatever the operands' dtype."""
    h, kernel = h.astype(jnp.float32), kernel.astype(jnp.float32)
    logits = jnp.dot(h, kernel.T if tied else kernel,
                     precision=jax.lax.Precision.HIGHEST)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(nll[:, None] * weights, axis=0)


def operands(rows, n_sums, tied, dtype, seed=0):
    rng = np.random.default_rng(seed)
    kernel = rng.normal(size=(VOCAB, HIDDEN) if tied else (HIDDEN, VOCAB))
    weights = rng.uniform(size=(rows, n_sums)) * (
        rng.uniform(size=(rows, n_sums)) < 0.8)
    if n_sums == 2:
        # the model's columns: a row counts in one of them
        weights[:, 0] *= np.arange(rows) % 2
        weights[:, 1] *= 1 - np.arange(rows) % 2
    return (jnp.asarray(rng.normal(size=(rows, HIDDEN)), dtype),
            jnp.asarray(0.3 * kernel, dtype),
            jnp.asarray(rng.integers(0, VOCAB, rows), jnp.int32),
            jnp.asarray(weights, jnp.float32))


def with_total(sums):
    return jnp.sum(sums), sums


FORMS = {
    "rule": lambda tied, *given: sparse_lm._streamed_nll(*given, CHUNK, tied),
    "parent": lambda tied, *given: with_total(
        parent_sums(*given, CHUNK, tied)),
    "plain": lambda tied, *given: with_total(plain_sums(*given, tied)),
}


@functools.lru_cache(maxsize=None)
def graded(form: str, tied: bool):
    """``(cotangent x total, sums)`` and the gradients with respect to ``h``
    and ``kernel``, jitted: the cotangent is an argument, so a shape
    compiles once for both."""
    def scaled(h, kernel, targets, weights, cotangent):
        total, sums = FORMS[form](tied, h, kernel, targets, weights)
        return cotangent * total, sums
    return jax.jit(jax.value_and_grad(scaled, (0, 1), has_aux=True))


@pytest.mark.parametrize("tied, n_sums, rows, cotangent", list(
    itertools.product((False, True), (1, 2), (ROWS, ROWS - 14), (1.0, 0.3))))
def test_the_rule_against_autodiff_and_the_parents_body(
        tied, n_sums, rows, cotangent):
    # (i) f32: the value, the reported sums and both gradients
    given = operands(rows, n_sums, tied, jnp.float32)
    (value, sums), made = graded("rule", tied)(*given, cotangent)
    (true_value, true_sums), truth = graded("plain", tied)(*given, cotangent)
    assert float(value) == pytest.approx(float(true_value), rel=1e-5)
    np.testing.assert_allclose(sums, true_sums, rtol=1e-5)
    for g, t in zip(made, truth):
        assert g.shape == t.shape and g.dtype == t.dtype
        assert rel_l2(g, t) < 1e-5
        np.testing.assert_allclose(g, t, rtol=1e-5,
                                   atol=1e-5 * float(jnp.max(jnp.abs(t))))

    # (ii) bf16 operands: no further from the truth (the same numbers'
    # gradients in f32) than the parent's replayed body: dx is scaled
    # before its one rounding, dW's sum is carried in bf16 as the
    # transposed scan carried it
    given = operands(rows, n_sums, tied, jnp.bfloat16)
    _, made = graded("rule", tied)(*given, cotangent)
    _, was = graded("parent", tied)(*given, cotangent)
    _, truth = graded("plain", tied)(
        *(x.astype(jnp.float32) for x in given[:2]), *given[2:], cotangent)
    for g, p, t in zip(made, was, truth):
        assert g.shape == p.shape and g.dtype == p.dtype == jnp.bfloat16
        assert 0 < rel_l2(g, t) <= 1.1 * rel_l2(p, t)

    # (iii) a derivative of the columns one by one is refused at trace time
    def unequal(h):
        sums = sparse_lm._streamed_nll(h, *given[1:], CHUNK, tied)[1]
        return sums[0] + 2.0 * sums[-1]
    with pytest.raises(TypeError, match="differentiates its total"):
        jax.grad(unequal)(given[0])


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(inner)


@pytest.mark.parametrize("tied", [False, True])
def test_three_products_a_chunk_and_a_plain_primal(tied):
    """Differentiated, a chunk's body holds the logits' product, ``dx``'s
    and ``dW``'s and nothing is computed again; undifferentiated, the one
    product of the plain scan."""
    given = operands(ROWS, 2, tied, jnp.bfloat16)
    total = lambda h, kernel: sparse_lm._streamed_nll(
        h, kernel, *given[2:], CHUNK, tied)[0]

    def census(fun):
        eqns = list(equations(jax.make_jaxpr(fun)(*given[:2]).jaxpr))
        scans = [e for e in eqns if e.primitive.name == "scan"]
        assert [e.params["length"] for e in scans] == [ROWS // CHUNK]
        products = [e for e in eqns if e.primitive.name == "dot_general"]
        assert all("head" in str(e.source_info.name_stack)
                   for e in products)
        assert not [e.primitive.name for e in eqns
                    if "remat" in e.primitive.name
                    or "checkpoint" in e.primitive.name]
        return len(products)

    assert census(jax.grad(total, (0, 1))) == 3
    assert census(total) == 1


TINY = dict(hidden_size=64, num_hidden_layers=1, num_dense_layers=0,
            num_heads=4, num_kv_heads=4, expert_width=32, num_experts=8,
            experts_per_token=2, experts_held=4, expert_offset=2,
            vocab_size=96, text_seq_len=24, image_grid=4, vocab_text=48,
            vocab_image=48, dtype="float32", head_chunk=16, dense_width=96,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16)


def test_a_prediction_modules_head_leaf_sums_both_uses(monkeypatch):
    """A tiny model with one prediction module: the head's leaf (and the
    table's, the final norms') get from the rule what they got from the
    parent's body, the main loss's use and the module's summed."""
    cfg = JoyAILMConfig(**TINY)
    cfg.validate()
    assert cfg.num_nextn_predict_layers == 1
    model = sparse_lm.build(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.zeros((2, 8), jnp.int32)
    # seeded weights of the initialiser's shapes (its program not compiled)
    params = jax.tree.map(
        lambda leaf: jnp.asarray(0.05 * rng.normal(size=leaf.shape),
                                 leaf.dtype) + (leaf.ndim == 1),
        jax.eval_shape(model.init, jax.random.PRNGKey(1), tokens, tokens))
    text = jnp.asarray(rng.integers(2, 48, (2, 24)), jnp.int32)
    image = jnp.asarray(rng.integers(0, 48, (2, 16)), jnp.int32)
    grads = lambda: jax.jit(jax.grad(
        lambda p: model.apply(p, text, image)[0]))(params)["params"]
    made = grads()

    monkeypatch.setattr(sparse_lm, "_streamed_nll",
                        lambda *args: with_total(parent_sums(*args)))
    was = grads()
    for leaf in ("lm_head", "token_emb", "final_norm"):
        assert rel_l2(made[leaf], was[leaf]) < 1e-5, leaf
    assert rel_l2(made["mtp"]["final_norm"], was["mtp"]["final_norm"]) < 1e-5
