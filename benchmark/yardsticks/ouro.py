"""The ``ouro`` yardstick: what the benchmark knows about the architecture
of Ouro-2.6B (ByteDance, ``model_type`` ``ouro``; config.json at
https://huggingface.co/ByteDance/Ouro-2.6B; the family's description:
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741) —
the plain reference that decides ``correct``, and the counts behind
``mfu_pct`` and ``attn_roofline``. Contract: the docstring of
``yardsticks/dalle.py``.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls;
``config.json`` pins the sizes, ``hidden_act``, ``rms_norm_eps``,
``rope_theta``, ``layer_types`` (every one ``full_attention``),
``use_sliding_window`` false, ``tie_word_embeddings`` false and
``total_ut_steps``; the rest is the family's description as the
configuration file's ``assumed`` states it):

    layer (four norms, no bias anywhere):
      a   = rmsnorm(x; attn_norm)
      q, k, v = a.W_q, a.W_k, a.W_v            H heads of d each (H = G)
      q, k <- rotary (rotate-half over all d, position = index)
      s_ij = q_i.k_j / sqrt(d), j <= i         causal over the whole sequence
      h   = x + rmsnorm(softmax(s) v . W_o; post_attn_norm)
      m   = rmsnorm(h; ff_norm)
      out = h + rmsnorm(W_down(silu(W_gate m) * (W_up m)); post_ff_norm)
    model, R = total_ut_steps, ONE set of leaves for the stack, the final
    norm, the gate and the head:
      x_0 = E[ids]
      z_t = rmsnorm(stack(x_{t-1}); final_norm);  x_t = z_t        t = 1..R
      lam_t = sigmoid(z_t . w_g + b_g)         a row's exit gate
      p_t = lam_t prod_{j<t} (1 - lam_j)  (t < R);  p_R = prod_{j<R} (1 - lam_j)
      nll_t = next-token cross-entropy of z_t . W_head, a row
      loss = mean over the T - 1 predicted rows of
             [ sum_t p_t nll_t - beta H(p) ],  H(p) = - sum_t p_t ln p_t

over ``[text || image + vocab_text]``; beta is ``exit_entropy_weight``.

**Departures from the published description, each as the configuration
file states it:**

- ``num_hidden_layers`` 6 stands for the published 48 (every layer alike:
  the period is one layer); ``vocab_size`` is a slice of the published
  vocabulary: embedding, head and loss are over the slice.
- what ``config.json`` has no key for is ``assumed``: that the final norm's
  output enters the next pass (``pass_input``), that the gate reads that
  normed state and has a bias (``exit_gate_input``, ``exit_gate_bias``),
  the objective itself (``exit_loss``: the description's
  entropy-regularised expectation over the exits; ``early_exit_threshold``
  is read at inference and by nothing here) and ``exit_entropy_weight``,
  no attention bias.
- the sequence reaches the model as the trainer's two fields, ``text`` and
  ``image`` (ids offset by ``vocab_text``), concatenated.
- ``embed_init_std`` and ``exit_gate_init_std`` (assumed) are the program's
  initialisers, not part of these equations: the reference takes the
  parameters it is given.

The leaves are the program's: ``token_emb``, ``passes/layer_<i>/...``,
``passes/final_norm``, ``exit_gate`` (hidden,), ``exit_gate_bias`` (1,),
``lm_head``. The passes are a Python loop. A leaf of the stack gets what
autodiff sums over its R uses.

What keeps the float32 reference inside one chip's memory at 8 192 tokens
changes no arithmetic: query rows go through attention in blocks, the
head's rows in chunks, token rows through the feed-forward in chunks, each
under ``jax.checkpoint``, and with ``checkpoint_blocks`` every layer is
and every pass around its layers too (R pass inputs and one pass's layer
inputs are alive, not R x layers of them; the loop over the passes is then
a ``lax.scan`` of that one body), the sequences go through one at a time,
and a layer's backward hands on its input's cotangent together with its
leaves' (``_together``: both sequences at once and nothing ordering the
weight gradients, the compiler's plan for this program was 21 GiB on the
chip: my chip run, PR 67).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

MASK_FILL = -1e30
QUERY_BLOCK = 128
HEAD_CHUNK = 1024
TOKEN_CHUNK = 2048


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotary(x, theta: float):
    """x: (B, T, heads, d). Rotate-half over all of d, position = index."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _block_size(t: int, want: int) -> int:
    return max(b for b in range(1, min(t, want) + 1) if t % b == 0)


def _attention(q, k, v):
    """q: (B, T, G, n, d) — n query heads to each of G key-value heads;
    k, v: (B, T, G, d). Dense causal masks, query rows a block at a time."""
    b, t, g, n, d = q.shape
    rows = _block_size(t, QUERY_BLOCK)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, start = args
        i = start + jnp.arange(rows)
        s = jnp.einsum("bqgnd,bkgd->bgnqk", qb, k) * d ** -0.5
        w = jax.nn.softmax(
            jnp.where(cols[None, :] <= i[:, None], s, MASK_FILL), -1)
        return jnp.einsum("bgnqk,bkgd->bqgnd", w, v)

    blocks = q.reshape(b, t // rows, rows, g, n, d).swapaxes(0, 1)
    out = jax.lax.map(block, (blocks, jnp.arange(t // rows) * rows))
    return out.swapaxes(0, 1).reshape(b, t, g * n * d)


def attention(a, attn, model: Mapping[str, Any]):
    b, t, _ = a.shape
    g, d = model["num_kv_heads"], model["head_dim"]
    n = model["num_heads"] // g
    q = jnp.dot(a, attn["q"]["kernel"]).reshape(b, t, g * n, d)
    k = jnp.dot(a, attn["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, attn["v"]["kernel"]).reshape(b, t, g, d)
    q, k = _rotary(q, model["rope_theta"]), _rotary(k, model["rope_theta"])
    ctx = _attention(q.reshape(b, t, g, n, d), k, v)
    return jnp.dot(ctx, attn["out"]["kernel"])


def gated_block(m, w):
    """``W_down(silu(W_gate m) * (W_up m))`` on every token, the tokens a
    chunk at a time. w: {"gate", "up", "down"} -> {"kernel"}."""
    flat = m.reshape(-1, m.shape[-1])
    rows = _block_size(flat.shape[0], TOKEN_CHUNK)

    @jax.checkpoint
    def chunk(x):
        hidden = jax.nn.silu(jnp.dot(x, w["gate"]["kernel"])) \
            * jnp.dot(x, w["up"]["kernel"])
        return jnp.dot(hidden, w["down"]["kernel"])

    return jax.lax.map(chunk, flat.reshape(-1, rows, flat.shape[-1])) \
        .reshape(m.shape)


def layer(p, x, model: Mapping[str, Any]):
    """One layer: attention and the gated block, each behind a norm and
    normed again before it joins the stream."""
    eps = model["rms_eps"]
    a = _rms_norm(x, p["attn_norm"], eps)
    h = x + _rms_norm(attention(a, p["attn"], model), p["post_attn_norm"],
                      eps)
    m = _rms_norm(h, p["ff_norm"], eps)
    return h + _rms_norm(gated_block(m, p["ff"]["dense"]),
                         p["post_ff_norm"], eps)


@jax.custom_vjp
def _together(x, leaves):
    """``(x, leaves)`` as they are; backward, their cotangents leave
    together. No arithmetic: it keeps the compiler from putting every
    layer's weight gradients off to the end of the backward pass, with
    what they read kept alive until then."""
    return x, leaves


_together.defvjp(lambda x, leaves: ((x, leaves), None),
                 lambda _, cts: jax.lax.optimization_barrier(cts))


def stack_pass(p, x, model: Mapping[str, Any]):
    """One pass: the layers, then the final norm. p: the stack's leaves
    (``layer_<i>``, ``final_norm``)."""
    for i in range(model["num_hidden_layers"]):
        x = layer(p[f"layer_{i}"], x, model)
    return _rms_norm(x, p["final_norm"], model["rms_eps"])


def stack_pass_in_blocks(p, x, model: Mapping[str, Any]):
    """:func:`stack_pass` as ``checkpoint_blocks`` runs it, the same
    arithmetic: every layer under a checkpoint, and taking its leaves
    through :func:`_together`, so that backward a layer's weight gradients
    are made before the state's cotangent goes on (24 layers' terms left
    waiting were 4.9 GiB of this program's plan)."""
    one = jax.checkpoint(lambda lp, x: layer(lp, x, model))
    for i in range(model["num_hidden_layers"]):
        x, lp = _together(x, p[f"layer_{i}"])
        x = one(lp, x)
    return _rms_norm(x, p["final_norm"], model["rms_eps"])


def exit_gate(z, p):
    """lam (B, T) of a pass's normed state z (B, T, D)."""
    return jax.nn.sigmoid(jnp.dot(z, p["exit_gate"]) + p["exit_gate_bias"][0])


def exit_distribution(lams):
    """(R, B, T): ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for t < R and the
    rest of the mass on the last pass (whose own gate nothing reads)."""
    left = jnp.ones_like(lams[0])
    out = []
    for lam in lams[:-1]:
        out.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(out + [left])


def exit_nll(z, head, ids):
    """(B, T - 1): the next-token cross-entropy of every predicting row of
    one exit's normed state z (B, T, D), the rows a chunk at a time."""
    b, t = ids.shape
    rows = _block_size(b * (t - 1), HEAD_CHUNK)

    @jax.checkpoint
    def chunk(args):
        h, target = args
        logp = jax.nn.log_softmax(jnp.dot(h, head), -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    return jax.lax.map(chunk, (
        z[:, :-1].reshape(-1, rows, z.shape[-1]),
        ids[:, 1:].reshape(-1, rows))).reshape(b, t - 1)


def exits(params, text, image, model: Mapping[str, Any],
          checkpoint_blocks: bool = False):
    """``(p, nll)``, each (R, B, T - 1): the exit distribution and the
    loss of every predicting row after every pass. The passes are a Python
    loop; with ``checkpoint_blocks`` the same loop as a ``lax.scan`` whose
    body is one pass under a checkpoint, its gate and its head (the program
    then holds one pass's layers, not R x that: a body a layer-application,
    its executable was 249 MB, more than the chip machine's compile cache
    takes, and every run compiled it again for 270 s: my chip runs, PR
    67)."""
    p = params["params"]
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    x = p["token_emb"][ids]
    head = p["token_emb"].T if model["tied_embeddings"] else p["lm_head"]

    def one_pass(x, _=None):
        x = (stack_pass_in_blocks if checkpoint_blocks else stack_pass)(
            p["passes"], x, model)          # the normed state goes round
        return x, (exit_gate(x, p)[:, :-1], exit_nll(x, head, ids))

    if checkpoint_blocks:
        _, (lams, nll) = jax.lax.scan(jax.checkpoint(one_pass), x, None,
                                      length=model["total_ut_steps"])
        return exit_distribution(list(lams)), nll
    lams, nll = [], []
    for _ in range(model["total_ut_steps"]):
        x, (lam, rows) = one_pass(x)
        lams.append(lam)
        nll.append(rows)
    return exit_distribution(lams), jnp.stack(nll)


def row_losses(params, text, image, model: Mapping[str, Any],
               checkpoint_blocks: bool = False):
    """``(rows, last)``, each (B, T - 1): every predicting row's ``sum_t p_t
    nll_t - beta H(p)``, and its cross-entropy after the LAST pass."""
    p, nll = exits(params, text, image, model, checkpoint_blocks)
    # 0 ln 0 = 0: a gate that saturates leaves no mass and no term
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), 0)
    return (jnp.sum(p * nll, 0) - model["exit_entropy_weight"] * entropy,
            nll[-1])


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False):
    """The rows' mean of ``sum_t p_t nll_t - beta H(p)``; returns ``(loss,
    (loss_text, loss_img))``, the LAST pass's mean cross-entropy over the
    targets of the two fields. With ``checkpoint_blocks`` the sequences go
    through one at a time, each under a checkpoint of its own (a sequence's
    rows depend on no other's: the mean is the same mean)."""
    if checkpoint_blocks and text.shape[0] > 1:
        one = jax.checkpoint(lambda seq: tuple(r[0] for r in row_losses(
            params, seq[0][None], seq[1][None], model, True)))
        rows, last = jax.lax.map(one, (text, image))
    else:
        rows, last = row_losses(params, text, image, model,
                                checkpoint_blocks)
    n_text = text.shape[1] - 1        # targets 1 .. text_len - 1
    return rows.mean(), (last[:, :n_text].mean(), last[:, n_text:].mean())


def loss_and_grads(params, text, image, model: Mapping[str, Any],
                   checkpoint_blocks: bool = False):
    """Loss and gradients of the mean over the sequences of ``text`` /
    ``image``: all of them through one jitted call or, with
    ``checkpoint_blocks``, a call a sequence of one jitted program that
    adds its sequence's share to the sums it is handed (every sequence has
    the same number of predicting rows, so the mean of the sequences' means
    is the mean over the rows; one sequence's program plans half the memory
    and compiles once, and one tree of sums is alive)."""
    n = text.shape[0] if checkpoint_blocks else 1

    def run(params, text, image, sums):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            (loss, _), grads = jax.value_and_grad(
                lambda q: loss_fn(q, text, image, model, checkpoint_blocks),
                has_aux=True)(params)
        return jax.tree.map(lambda s, a: s + a / n, sums, (loss, grads))

    run = jax.jit(run, donate_argnums=3)
    sums = jax.jit(lambda q: (jnp.zeros((), jnp.float32), jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), q)))(params)
    if not checkpoint_blocks:
        return run(params, text, image, sums)
    for i in range(n):
        sums = run(params, text[i:i + 1], image[i:i + 1], sums)
    return sums


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def layer_applications(model: Mapping[str, Any]) -> int:
    """Times a layer's equations run on a sample: the depth x the passes."""
    return model["num_hidden_layers"] * model["total_ut_steps"]


def attention_pairs(model: Mapping[str, Any]) -> int:
    """Allowed (query, key) pairs of one head of one sequence."""
    t = tokens_per_sample(model)
    return t * (t + 1) // 2


def attention_flops_forward(model: Mapping[str, Any]) -> int:
    """QK^T and PV of one sequence in one layer-application, all query
    heads, allowed pairs only."""
    return (4 * attention_pairs(model) * model["head_dim"]
            * model["num_heads"])


def layer_matmul_params(model: Mapping[str, Any]) -> int:
    """Weights one token is multiplied by in one layer-application: q and
    out (hidden x H d each), k and v (hidden x G d), and the gated block's
    three (hidden x dense_width)."""
    return model["hidden_size"] * (
        model["head_dim"] * 2 * (model["num_heads"] + model["num_kv_heads"])
        + 3 * model["dense_width"])


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only — every layer-application's seven products and the pairs inside
    the causal band (depth x passes of them: the parameters are shared, the
    work is not), and after EVERY pass the gate's product over the rows and
    the head over the predicted positions. A replay under rematerialisation
    is the program's choice and not counted."""
    t, passes = tokens_per_sample(model), model["total_ut_steps"]
    fwd = layer_applications(model) * (
        2.0 * t * layer_matmul_params(model) + attention_flops_forward(model))
    fwd += passes * 2.0 * model["hidden_size"] * (
        t + model["vocab_size"] * (t - 1))
    return 3.0 * fwd


def _least(calls, peaks: Mapping[str, float]) -> Dict[str, float]:
    """calls: (flops, bytes) per kernel call; each costs the larger of
    flops / peak and bytes / bandwidth."""
    total = by_bytes = 0.0
    for flops, nbytes in calls:
        t_flops = flops / peaks["bf16_flops_per_s"]
        t_bytes = nbytes / peaks["hbm_bytes_per_s"]
        total += max(t_flops, t_bytes)
        by_bytes += t_bytes if t_bytes >= t_flops else 0.0
    return {"seconds": total, "bandwidth_bound_share": by_bytes / total}


def attention_min_seconds_per_sample(model: Mapping[str, Any],
                                     peaks: Mapping[str, float],
                                     act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the attention kernels of one
    sample's forward and backward pass: one forward and one backward call a
    layer-application (depth x passes). Forward reads q and writes the
    context (T x H x d each) and reads k, v (T x G x d each); backward reads
    q, context, its cotangent, k, v and writes dq, dk, dv, at twice the
    flops. A rematerialised layer keeps its attention's output, so no
    replay runs the kernel again; one that did would be the program's
    choice and not counted, as in ``train_flops_per_sample``."""
    t, d = tokens_per_sample(model), model["head_dim"]
    wide = t * model["num_heads"] * d * act_bytes
    narrow = t * model["num_kv_heads"] * d * act_bytes
    flops = attention_flops_forward(model)
    calls = [(flops, 2 * wide + 2 * narrow),
             (2 * flops, 4 * wide + 4 * narrow)] * layer_applications(model)
    return _least(calls, peaks)
