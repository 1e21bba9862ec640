"""The ``keye`` yardstick: what the benchmark knows about the language
model of Keye-VL-2.0-30B-A3B (Kwai-Keye, ``model_type`` ``KeyeVL2``;
config.json at https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B; the
vision tower is no part of it) — the plain reference that decides
``correct``, and the counts behind ``mfu_pct``, ``attn_roofline``,
``indexer_select_roofline`` and ``moe_experts_roofline``. Contract: the
docstring of ``yardsticks/dalle.py``.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls;
``config.json`` pins the sizes, ``rope_theta``, ``mrope_section``,
``norm_topk_prob`` and ``sa_config``; the rest is what the configuration
file's ``assumed`` states, each a key of ``model`` that this file reads).
Every layer, with ``sg`` = stop_gradient:

    a      = rmsnorm(x; attn_norm)
    q,k,v  = a.W_q (H x d), a.W_k (G x d), a.W_v (G x d)     no biases
    q,k    = rmsnorm(q; q_norm), rmsnorm(k; k_norm)   qk_norm: over each
                                            head's d, one vector of d each
    q,k   <- rotary, rotate-half over d at theta; frequency pair i of d/2
             reads position row 0 for i < 16, row 1 for 16 <= i < 40, row 2
             for 40 <= i < 64                        mrope_section
    indexer (reads sg(a): no loss's gradient passes through it to x):
      qI   = sg(a).W_qI (J x e);  kI = layernorm(sg(a).W_kI; scale, bias)
             (ONE head of e);  w = sg(a).W_w (J)
      qI,kI <- rotary, rotate-half over e at theta, position row 0
      I[t,s] = (J e)^-1/2 sum_j w[t,j] relu(qI[t,j] . kI[s])
      S_t  = the index_topk largest I[t,s] over s <= t (``lax.top_k``:
             ties to the lower s); every s <= t where t < index_topk
    P_h[t,s] = softmax over s in S_t of q_h[t] . k_g(h)[s] / sqrt(d)
    h      = x + concat_h(sum_s P_h[t,s] v_g(h)[s]) . W_o
    L_I   += mean_t KL(sg(1/H sum_h P_h[t, .]) || softmax_{s in S_t} I[t,s])
    m      = rmsnorm(h; ff_norm)
    r      = m . W_r (num_experts);  S = the k largest;  p = softmax(r_S)
    out    = h + sum_{e in S, e held} p_e . W_down,e(silu(W_gate,e m)
                                                     * W_up,e m)

then a final RMSNorm, an untied head, the mean next-token cross-entropy
``L_main`` over the T - 1 predicted positions of ``[text || image +
vocab_text]``, and ``loss = L_main + indexer_loss_weight * L_I``. By
construction L_main's gradient on the indexer's leaves is nought and L_I's
on every other leaf is.

**The position rows** (``position_rows``; assumed ``position_rule``): a
``text`` token at index i has (i, i, i); the ``image`` field's token at
grid (r, c) has (T_text, T_text + r, T_text + c).

**Departures from the published description**, each as the configuration
file states it: ``experts_held`` of the ``num_experts`` routed experts are
held (the router scores all, the sum is over the held ones;
``whole_layer_experts`` gives the uncut layer for the test that adds the
shares up); ``vocab_size`` is a slice; ``num_hidden_layers`` 7 stand for 48
alike; the sequence reaches the model as the trainer's two fields.

**Near-ties.** Both the k largest router scores and the ``index_topk``
largest index scores are discontinuous: the program's bfloat16 products and
this float32 reference choose different sets for a few tokens in a hundred
and a few keys in each query's thousands. ``chosen_keys`` returns this
reference's sets (as dense masks) for ``probes/keye_precision.py`` to
count.

What keeps the float32 reference inside one chip's memory at 8 192 tokens
changes no arithmetic: the indexer's scores, the selection, attention and
the KL go through the query rows in blocks, the head's rows in chunks, the
held experts one at a time, each under ``jax.checkpoint``, and with
``checkpoint_blocks`` every layer is too; and the layers, all alike, run as
one ``lax.scan`` over their stacked parameters (``_run_layers`` says why).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

MASK_FILL = -1e30
QUERY_BLOCK = 128
HEAD_CHUNK = 2048


def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def position_rows(model: Mapping[str, Any]) -> np.ndarray:
    """(3, T) int32: the rule of the module docstring."""
    text, grid = model["text_seq_len"], model["image_grid"]
    i = np.arange(text)
    r, c = np.divmod(np.arange(grid * grid), grid)
    rows = [np.concatenate([i, np.full(grid * grid, text)]),
            np.concatenate([i, text + r]), np.concatenate([i, text + c])]
    return np.stack(rows).astype(np.int32)


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, positions, theta: float):
    """x: (B, T, heads, d). Rotate-half over all of d; positions: (T,) one
    row for every pair, or (T, d/2) a position for each frequency pair."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    pos = jnp.asarray(positions, jnp.float32)
    ang = (pos[:, None] if pos.ndim == 1 else pos) * freqs
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _pair_positions(rows, sections):
    """(T, d/2): the position each frequency pair reads."""
    row_of_pair = np.repeat(np.arange(len(sections)), sections)
    return jnp.asarray(rows, jnp.float32).T[:, row_of_pair]


def _block_size(t: int, want: int) -> int:
    return max(b for b in range(1, min(t, want) + 1) if t % b == 0)


def _chosen_mask(scores, rows, topk: int):
    """(B, R, T) bool: for the queries ``rows`` (R,) with index scores
    ``scores`` (B, R, T), the keys of each one's set."""
    t = scores.shape[-1]
    causal = jnp.arange(t)[None, :] <= rows[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, t))
    hit = jnp.zeros(scores.shape, bool)
    b, r = np.indices(scores.shape[:2])
    hit = hit.at[b[..., None], r[..., None], idx].set(True)
    return hit & causal


def _attention_and_alignment(q, k, v, qi, ki, w, topk: int, sets=None):
    """The selected attention and the indexer's loss of one layer, the
    query rows a block at a time. q: (B, T, G, n, d); k, v: (B, T, G, d);
    qi: (B, T, J, e); ki: (B, T, e); w: (B, T, J). ``sets``: (B, T, T) bool
    to attend by instead of the reference's own. Returns the context
    (B, T, G n d), the rows' KL summed (B,), and the sets (B, T, T)."""
    b, t, g, n, d = q.shape
    rows = _block_size(t, QUERY_BLOCK)
    scale = (qi.shape[2] * qi.shape[3]) ** -0.5

    @jax.checkpoint
    def block(args):
        qb, qib, wb, start, given = args
        i = start + jnp.arange(rows)
        z = jnp.einsum("bqje,bke->bqjk", qib, ki)
        index = scale * jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(z), wb)
        on = given if sets is not None else _chosen_mask(
            jax.lax.stop_gradient(index), i, topk)
        s = jnp.einsum("bqgnd,bkgd->bgnqk", qb, k) * d ** -0.5
        prob = jax.nn.softmax(jnp.where(on[:, None, None], s, MASK_FILL), -1)
        ctx = jnp.einsum("bgnqk,bkgd->bqgnd", prob, v)
        target = jax.lax.stop_gradient(jnp.mean(prob, axis=(1, 2)))
        log_sigma = jax.nn.log_softmax(jnp.where(on, index, MASK_FILL), -1)
        kl = jnp.sum(jnp.where(
            on, jax.scipy.special.xlogy(target, target) - target * log_sigma,
            0.0), axis=(1, 2))
        return ctx, kl, on

    split = lambda x: x.reshape(b, t // rows, rows, *x.shape[2:]).swapaxes(
        0, 1)
    given = split(sets) if sets is not None else jnp.zeros(
        (t // rows, b, rows, 1), bool)
    ctx, kl, on = jax.lax.map(block, (
        split(q), split(qi), split(w), jnp.arange(t // rows) * rows, given))
    join = lambda x: x.swapaxes(0, 1).reshape(b, t, *x.shape[3:])
    return join(ctx).reshape(b, t, g * n * d), jnp.sum(kl, 0), join(on)


def route(m, ff, model: Mapping[str, Any], chosen=None):
    """The k experts of every token and their weights: the k largest
    router scores (with ``chosen`` those ids stand for them) and their
    softmax (``norm_topk_prob``)."""
    scores = jnp.dot(m, ff["router"])
    if chosen is None:
        _, chosen = jax.lax.top_k(scores, model["experts_per_token"])
    return chosen, jax.nn.softmax(
        jnp.take_along_axis(scores, chosen, -1), -1)


def expert_sum(m, idx, p, experts, first: int):
    """sum over the experts of ``experts`` (leaves stacked on the leading
    axis; the first is expert ``first`` of the router's) of routing weight
    x expert(m); a token not routed to an expert weighs 0 there."""
    @jax.checkpoint
    def one(y, xs):
        e, gate, up, down = xs
        weight = jnp.sum(jnp.where(idx == e, p, 0.0), -1)
        out = jnp.dot(jax.nn.silu(jnp.dot(m, gate)) * jnp.dot(m, up), down)
        return y + weight[..., None] * out, None

    n = experts["gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (first + jnp.arange(n), experts["gate"],
                         experts["up"], experts["down"]))
    return y


def whole_layer_experts(m, ff, model: Mapping[str, Any]):
    """The uncut expert layer: ``ff["experts"]`` holds all of the
    router's."""
    idx, p = route(m, ff, model)
    return expert_sum(m, idx, p, ff["experts"], 0)


def _layer(p, x, rows, model: Mapping[str, Any], sets=None, chosen=None):
    """One layer: (x', the rows' KL summed (B,), the sets, m)."""
    b, t, _ = x.shape
    g, d, eps = model["num_kv_heads"], model["head_dim"], model["rms_eps"]
    n = model["num_heads"] // g
    theta = model["rope_theta"]
    attn = p["attn"]
    a = _rms_norm(x, p["attn_norm"], eps)
    q = jnp.dot(a, attn["q"]["kernel"]).reshape(b, t, g * n, d)
    k = jnp.dot(a, attn["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, attn["v"]["kernel"]).reshape(b, t, g, d)
    if model["qk_norm"]:
        q, k = _rms_norm(q, attn["q_norm"], eps), \
            _rms_norm(k, attn["k_norm"], eps)
    by_pair = _pair_positions(rows, model["mrope_section"])
    q, k = _rotary(q, by_pair, theta), _rotary(k, by_pair, theta)

    ix = attn["indexer"]
    a_i = jax.lax.stop_gradient(a)
    heads, dim = model["index_heads"], model["index_head_dim"]
    qi = jnp.dot(a_i, ix["q"]["kernel"]).reshape(b, t, heads, dim)
    ki = _layer_norm(jnp.dot(a_i, ix["k"]["kernel"]), ix["k_norm"], eps)
    w = jnp.dot(a_i, ix["weights"]["kernel"])
    if model["indexer_rotary"]:
        qi = _rotary(qi, rows[0], theta)
        ki = _rotary(ki[:, :, None], rows[0], theta)[:, :, 0]

    ctx, kl, sets = _attention_and_alignment(
        q.reshape(b, t, g, n, d), k, v, qi, ki, w, model["index_topk"], sets)
    h = x + jnp.dot(ctx, attn["out"]["kernel"])
    m = _rms_norm(h, p["ff_norm"], eps)
    idx, weights = route(m, p["ff"], model, chosen)
    f = expert_sum(m, idx, weights, p["ff"]["experts"],
                   model["expert_offset"])
    return h + f, kl, sets, m


def _embed(p, text, image, model: Mapping[str, Any]):
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    return ids, p["token_emb"][ids]


def _rows_of(text, image, model: Mapping[str, Any]) -> np.ndarray:
    """The position rows of a batch's two fields (the configuration's
    lengths, or shorter ones in a test)."""
    return position_rows({"text_seq_len": text.shape[1],
                          "image_grid": model["image_grid"]})[
                              :, :text.shape[1] + image.shape[1]]


def _stacked(p, model: Mapping[str, Any]):
    """The layers' parameters, alike in shape, stacked on a leading axis,
    and the rest of the tree."""
    layers = [p[f"layer_{i}"] for i in range(model["num_hidden_layers"])]
    rest = {k: v for k, v in p.items() if not k.startswith("layer_")}
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *layers), rest


def _run_layers(stacked, x, rows, model, checkpoint_blocks, sets=None):
    """The layer stack as ONE ``lax.scan`` over the stacked parameters (a
    departure from "plain", as ``yardsticks/dalle.py``'s: unrolled, the
    float32 program of seven layers is a 133 MB executable, which with the
    system's own step does not fit the chip machine's compile cache; it
    changes no arithmetic). Returns (x', sum over layers and samples of
    the rows' KL, the layers' sets (layers, B, T, T))."""
    def layer(carry, xs):
        x, total = carry
        lp, given = xs
        x, kl, chosen, _ = _layer(lp, x, rows, model, given)
        return (x, total + jnp.sum(kl)), chosen

    if checkpoint_blocks:
        layer = jax.checkpoint(layer)
    (x, total), chosen = jax.lax.scan(layer, (x, jnp.float32(0.0)),
                                      (stacked, sets))
    return x, total, chosen


def chosen_keys(params, text, image, model: Mapping[str, Any]):
    """(layers, B, T, T) bool: the keys every query chooses in every layer,
    in float32 (what ``probes/keye_precision.py`` sets the program's
    choices against: near-ties flip)."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        with jax.default_matmul_precision("highest"):
            _, x = _embed(p, text, image, model)
            return _run_layers(_stacked(p, model)[0], x,
                               _rows_of(text, image, model), model, False)[2]
    return jax.jit(run)(params, text, image)


def _loss_of(stacked, rest, text, image, model, checkpoint_blocks, sets):
    ids, x = _embed(rest, text, image, model)
    b, t = ids.shape
    x, align, _ = _run_layers(stacked, x, _rows_of(text, image, model),
                              model, checkpoint_blocks, sets)
    align = align / (b * t)
    x = _rms_norm(x, rest["final_norm"], model["rms_eps"])
    rows = _block_size(b * (t - 1), HEAD_CHUNK)

    @jax.checkpoint
    def chunk(args):
        h, target = args
        logp = jax.nn.log_softmax(jnp.dot(h, rest["lm_head"]), -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    nll = jax.lax.map(chunk, (
        x[:, :-1].reshape(-1, rows, x.shape[-1]),
        ids[:, 1:].reshape(-1, rows)))
    main = nll.mean()
    return main + model["indexer_loss_weight"] * align, (main, align)


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False, sets=None):
    """``L_main + indexer_loss_weight * L_I``; returns ``(loss, (L_main,
    L_I))``. ``sets``: (layers, B, T, T) bool to attend by (module
    docstring, near-ties); None: the reference's own."""
    return _loss_of(*_stacked(params["params"], model), text, image, model,
                    checkpoint_blocks, sets)


def _loss_and_grads(params, text, image, model, checkpoint_blocks, sets):
    """The gradients are taken of the stacked layers and unstacked after the
    program has run, a leaf at a time: inside it they would stand twice."""
    def run(params, text, image, sets):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            (loss, _), grads = jax.value_and_grad(
                lambda stacked, rest: _loss_of(
                    stacked, rest, text, image, model, checkpoint_blocks,
                    sets), argnums=(0, 1), has_aux=True)(
                        *_stacked(params["params"], model))
        return loss, grads
    loss, (stacked, rest) = jax.jit(run)(params, text, image, sets)
    layers = {f"layer_{i}": jax.tree.map(lambda a, i=i: a[i], stacked)
              for i in range(model["num_hidden_layers"])}
    return loss, {"params": {**rest, **layers}}


def loss_and_grads(params, text, image, model: Mapping[str, Any],
                   checkpoint_blocks: bool = False):
    """Loss and gradients of the mean over the sequences of ``text`` /
    ``image``: all of them through one jitted call."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           None)


def loss_and_grads_at(sets, params, text, image, model: Mapping[str, Any],
                      checkpoint_blocks: bool = False):
    """:func:`loss_and_grads` at the key sets ``sets`` (layers, B, T, T)
    bool instead of the reference's own."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           jnp.asarray(sets))


# -- the counts: operations and bytes from shapes alone ----------------------

def causal_pairs(model: Mapping[str, Any]) -> int:
    t = tokens_per_sample(model)
    return t * (t + 1) // 2


def selected_pairs(model: Mapping[str, Any]) -> int:
    """(query, key) pairs of one head of one sequence that are in a set:
    min(t + 1, index_topk) a query. 14 681 088 of 33 558 528 causal pairs at
    8 192 tokens and 2 048 keys."""
    seen = np.minimum(np.arange(tokens_per_sample(model)) + 1,
                      model["index_topk"])
    return int(seen.sum())


def attention_flops_forward(model: Mapping[str, Any]) -> int:
    """QK^T and PV of one sequence and layer, all query heads, over the
    selected pairs only."""
    return (4 * selected_pairs(model) * model["head_dim"]
            * model["num_heads"])


def indexer_flops_forward(model: Mapping[str, Any]) -> int:
    """The indexer's scores of one sequence and layer: every head's product
    over every causal pair, which selecting requires."""
    return (2 * causal_pairs(model) * model["index_heads"]
            * model["index_head_dim"])


def held_assignments_per_token(model: Mapping[str, Any]) -> float:
    return (model["experts_per_token"] * model["experts_held"]
            / model["num_experts"])


def layer_matmul_params(model: Mapping[str, Any]) -> float:
    """Weights one token is multiplied by in one layer: attention's four
    products, the indexer's three, the router, and the held experts it is
    routed to (in expectation)."""
    hidden, d = model["hidden_size"], model["head_dim"]
    attention = hidden * d * 2 * (model["num_heads"] + model["num_kv_heads"])
    indexer = hidden * (model["index_heads"] * model["index_head_dim"]
                        + model["index_head_dim"] + model["index_heads"])
    expert = 3 * hidden * model["expert_width"]
    return (attention + indexer + hidden * model["num_experts"]
            + held_assignments_per_token(model) * expert)


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work only
    — the projections, the router, the held experts' products for the
    assignments they receive in expectation, attention over the selected
    pairs, the indexer's scores over every causal pair, the sliced head over
    the predicted positions. The heads' mean probability (the loss's
    target) is computed from products already counted."""
    t, layers = tokens_per_sample(model), model["num_hidden_layers"]
    fwd = 2.0 * t * layers * layer_matmul_params(model)
    fwd += layers * (attention_flops_forward(model)
                     + indexer_flops_forward(model))
    fwd += 2.0 * model["hidden_size"] * model["vocab_size"] * (t - 1)
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
    sample's forward and backward pass, **over the selected pairs only**: a
    lowering that multiplies every causal tile reads at most 44% of what
    its products alone would. Forward reads q and writes the context (T x H
    x d each) and reads k, v (T x G x d each); backward reads q, context,
    its cotangent, k, v and writes dq, dk, dv, at twice the flops."""
    t, d = tokens_per_sample(model), model["head_dim"]
    wide = t * model["num_heads"] * d * act_bytes
    narrow = t * model["num_kv_heads"] * d * act_bytes
    flops = attention_flops_forward(model)
    calls = [(flops, 2 * wide + 2 * narrow),
             (2 * flops, 4 * wide + 4 * narrow)] * model["num_hidden_layers"]
    return _least(calls, peaks)


def indexer_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend scoring and selecting in one
    sample's forward and backward pass: a layer and direction the larger of
    the causal pairs' products over the peak (twice the forward's in the
    backward: the queries' and the key's cotangents) and the bytes no
    lowering avoids over the bandwidth: qI, kI and w read once and one
    selection written (a bit a causal pair), their cotangents written and
    the selection read in the backward. A replay under rematerialisation
    and the counting that selects are work the program chose: in the
    measured time, not here."""
    t = tokens_per_sample(model)
    heads, dim = model["index_heads"], model["index_head_dim"]
    operands = t * ((heads + 1) * dim * act_bytes + heads * 4)
    selection = causal_pairs(model) / 8
    flops = indexer_flops_forward(model)
    calls = [(flops, operands + selection),
             (2 * flops, 2 * operands + selection)
             ] * model["num_hidden_layers"]
    return _least(calls, peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: three products a
    layer over the assignments the held experts receive in expectation.
    Bytes are the rows in and out (the weights are read once for all the
    samples of a step's micro-batch)."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 3 * dim * width * rows
    nbytes = rows * (2 * dim + 3 * width) * act_bytes
    calls = [(flops, nbytes),
             (2 * flops, 2 * nbytes)] * model["num_hidden_layers"]
    return _least(calls, peaks)
