"""The ``joyai`` yardstick: what the benchmark knows about the
architecture of JoyAI-LLM-Flash (jdopensource, ``model_type``
``joyai_llm_flash``; config.json at
https://huggingface.co/jdopensource/JoyAI-LLM-Flash) — the plain reference
that decides ``correct``, and the counts behind ``mfu_pct``,
``attn_roofline`` and ``moe_experts_roofline``. Contract: the docstring of
``yardsticks/dalle.py``.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls;
``config.json`` pins every size, the router (``scoring_func`` sigmoid,
``topk_method`` ``noaux_tc`` with ``n_group`` 1 and ``topk_group`` 1: no
group limit, ``norm_topk_prob``, ``routed_scaling_factor``),
``rope_interleave`` and ``num_nextn_predict_layers``; the rest is the
public description of the family, DeepSeek-V3 report sections 2.1 and 2.2,
as the configuration file's ``assumed`` states it):

    x0    = E[ids]                                          no multiplier
    a     = rmsnorm(x; attn_norm)
    c_q   = rmsnorm(a . W_qa; q_a_norm)                     q_lora_rank
    [q_nope ; q_rope] = c_q . W_qb      H x nope columns, then H x rope
    [c_kv ; k_rope]   = a . W_kva       kv_lora_rank columns, then rope:
                                        ONE rotary key for all H heads
    [k_nope ; v] = rmsnorm(c_kv; kv_a_norm) . W_kvb
                                        H x nope columns, then H x v
    q_rope, k_rope <- rotary: the pair (x_2i, x_2i+1) as a complex number
                      times exp(i . pos . theta^(-2i / rope)); no scaling
    s_h   = (q_nope,h . k_nope,h + q_rope,h . k_rope) / sqrt(nope + rope)
            key <= query, every layer over the whole sequence
    h     = x + concat_h(softmax(s_h) v_h) . W_o            no gate, no bias
    m     = rmsnorm(h; ff_norm)
    dense layer (the leading num_dense_layers):
        out = h + W_down(silu(W_gate m) * (W_up m))         dense_width
    expert layer:
        s = sigmoid(m . W_r)                                num_experts
        S = the k largest of s + b      b: router_bias, zeros; no gradient
        p_e = route_scale * s_e / (sum_{e in S} s_e + 1e-20)
        out = h + shared(m) + sum_{e in S, e held} p_e . expert_e(m)

then a final RMSNorm ``z``, the untied head and the mean next-token
cross-entropy ``loss_main`` over the T - 1 predicted positions of ``[text
|| image + vocab_text]``. **The prediction module** (one;
``num_nextn_predict_layers``): for position i,

    h'_i = [rmsnorm(E[t_{i+1}]; enorm) ; rmsnorm(z_i; hnorm)] . W_eh

one more expert layer as above on ``h'`` (parameters ``mtp/block``), a
final norm of its own, **the same E and the same head**, cross-entropy
against ``t_{i+2}`` over the T - 2 positions that have one: ``loss_mtp``.
The loss is ``loss_main + mtp_loss_weight * loss_mtp``.

**Departures from the published description, each as the configuration
file states it:**

- ``experts_held`` of the ``num_experts`` routed experts are held (from
  ``expert_offset``): the router scores all of them and the sum is over
  the held ones only; the shared expert is whole. What the absent experts
  would add is left out here as in the program (guide ``model-configs``
  section 4); ``whole_layer_experts`` gives the uncut layer for the test
  that adds the shares up, the shared expert counted once.
- ``vocab_size`` is a slice of the published vocabulary; ``num_hidden_
  layers`` 5 stands for the published 40 (layers 0-4).
- the columns of ``W_qb``, ``W_kva`` and ``W_kvb`` are ordered part by part
  (every head's ``nope`` columns, then every head's ``rope`` columns; the
  latent, then the rotary key; every head's ``k_nope``, then every head's
  ``v``), where the released checkpoints interleave them head by head: a
  fixed permutation of columns, the same function.
- **the bias b is never updated** (the source steps it by the sign of
  each expert's load; not in this program yet): a leaf whose gradient is
  exactly zero here and in the program. No auxiliary loss.
- ``assumed``: ``mtp_loss_weight`` (no key; 0.3, the report's value for
  most of its run), ``z`` taken AFTER the main model's final norm and the
  embedding FIRST in the concatenation (the released checkpoints' layout:
  ``enorm``, ``hnorm``, ``eh_proj``), the router's input (``m``).
- the prediction module runs on all T positions: position T - 1 reads a
  next token that does not exist (any id: causality keeps the row to
  itself and it is not scored); here token 0 of the sequence.
- the sequence reaches the model as the trainer's two fields, ``text`` and
  ``image`` (ids offset by ``vocab_text``), concatenated.

**Near-ties**, as in ``yardsticks/trinity.py``: the k largest of s + b is
not continuous; ``loss_and_grads_at`` evaluates the reference at given
sets (``probes/joyai_precision.py`` reads both).

What keeps the float32 reference inside one chip's memory at 8 192 tokens
changes no arithmetic: query rows go through attention in blocks, the
head's rows in chunks, token rows through the dense block and the shared
expert in chunks, the held experts one at a time, each under
``jax.checkpoint``, and with ``checkpoint_blocks`` every layer is too.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

MASK_FILL = -1e30
QUERY_BLOCK = 256
HEAD_CHUNK = 2048
TOKEN_CHUNK = 4096


def blocks(model: Mapping[str, Any]) -> int:
    """Layers that run: the main model's and the prediction module's."""
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def expert_layers(model: Mapping[str, Any]) -> int:
    return blocks(model) - model["num_dense_layers"]


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary_pairs(x, theta: float):
    """x: (B, T, ..., d). The pair (x_2i, x_2i+1) is the complex number
    x_2i + i x_2i+1, multiplied by exp(i . pos . theta^(-2i / d))."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape(1, x.shape[1], *(1,) * (x.ndim - 3), d // 2)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     -1).reshape(x.shape)


def _block_size(t: int, want: int) -> int:
    return max(b for b in range(1, min(t, want) + 1) if t % b == 0)


def _attention(q_nope, q_rope, k_nope, k_rope, v):
    """q_nope, k_nope: (B, T, H, n); q_rope: (B, T, H, r); k_rope: (B, T,
    r), one for all heads; v: (B, T, H, d). Dense masks, one sequence's
    query rows a block at a time."""
    b, t, h, n = q_nope.shape
    scale = (n + q_rope.shape[-1]) ** -0.5
    rows = _block_size(t, QUERY_BLOCK)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qn, qr, seq, start = args
        allowed = cols[None, :] <= (start + jnp.arange(rows))[:, None]
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope[seq])
             + jnp.einsum("qhd,kd->hqk", qr, k_rope[seq])) * scale
        w = jax.nn.softmax(jnp.where(allowed, s, MASK_FILL), -1)
        return jnp.einsum("hqk,khd->qhd", w, v[seq])

    split = lambda x: x.reshape(b * (t // rows), rows, h, -1)
    out = jax.lax.map(block, (
        split(q_nope), split(q_rope), jnp.repeat(jnp.arange(b), t // rows),
        jnp.tile(jnp.arange(t // rows) * rows, b)))
    return out.reshape(b, t, -1)


def latent_attention(a, attn, model: Mapping[str, Any]):
    """The attention of one layer on its normed input ``a`` (B, T, hidden),
    before the residual. attn: the layer's ``attn`` parameters."""
    b, t, _ = a.shape
    h, eps = model["num_heads"], model["rms_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank = model["kv_lora_rank"]
    c_q = _rms_norm(jnp.dot(a, attn["q_a"]["kernel"]), attn["q_a_norm"], eps)
    q = jnp.dot(c_q, attn["q_b"]["kernel"])
    kv = jnp.dot(a, attn["kv_a"]["kernel"])
    c_kv = _rms_norm(kv[..., :rank], attn["kv_a_norm"], eps)
    k_rope = kv[..., rank:]
    kv = jnp.dot(c_kv, attn["kv_b"]["kernel"])
    heads = lambda x: x.reshape(b, t, h, -1)
    q_nope, q_rope = heads(q[..., :h * nope]), heads(q[..., h * nope:])
    k_nope, v = heads(kv[..., :h * nope]), heads(kv[..., h * nope:])
    theta = model["rope_theta"]
    ctx = _attention(q_nope, rotary_pairs(q_rope, theta), k_nope,
                     rotary_pairs(k_rope, theta), v)
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


def route(m, ff, model: Mapping[str, Any], chosen=None):
    """The k experts of every token and their weights: (ids, weights),
    each (..., k). Sigmoid scores in f32; the k largest of score + bias
    (with ``chosen`` (..., k) those ids stand for them); the weights are
    the chosen experts' scores without the bias, over their sum, times
    ``route_scale``."""
    scores = jax.nn.sigmoid(jnp.dot(m, ff["router"]))
    if chosen is None:
        select = scores + jax.lax.stop_gradient(ff["router_bias"])
        _, chosen = jax.lax.top_k(select, model["experts_per_token"])
    top = jnp.take_along_axis(scores, chosen, -1)
    top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return chosen, top * model["route_scale"]


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
    router's, the shared expert is added once."""
    idx, p = route(m, ff, model)
    return expert_sum(m, idx, p, ff["experts"], 0) \
        + gated_block(m, ff["shared"])


def _layer(p, x, dense: bool, model: Mapping[str, Any], chosen=None):
    """One layer; returns its output and the ``m`` its router read."""
    eps = model["rms_eps"]
    h = x + latent_attention(_rms_norm(x, p["attn_norm"], eps), p["attn"],
                             model)
    m = _rms_norm(h, p["ff_norm"], eps)
    if dense:
        return h + gated_block(m, p["ff"]["dense"]), m
    idx, weights = route(m, p["ff"], model, chosen)
    f = expert_sum(m, idx, weights, p["ff"]["experts"],
                   model["expert_offset"])
    return h + f + gated_block(m, p["ff"]["shared"]), m


def _ids(text, image, model: Mapping[str, Any]):
    return jnp.concatenate([text, image + model["vocab_text"]], 1)


def _states(p, ids, model: Mapping[str, Any], checkpoint_blocks, chosen,
            keep_m: bool = False):
    """The main model's normed last state ``z`` and the prediction
    module's (None without one); with ``keep_m`` also what every expert
    layer's router read, in order."""
    dense = model["num_dense_layers"]
    wrap = jax.checkpoint if checkpoint_blocks else (lambda f: f)
    kept, given = [], iter(() if chosen is None else chosen)

    def run(lp, x, is_dense):
        sets = None if is_dense else next(given, None)
        x, m = wrap(lambda lp, x, sets: _layer(lp, x, is_dense, model,
                                               sets))(lp, x, sets)
        if not is_dense:
            kept.append((m, lp["ff"]))
        return x

    x = p["token_emb"][ids]
    for i in range(model["num_hidden_layers"]):
        x = run(p[f"layer_{i}"], x, i < dense)
    z = _rms_norm(x, p["final_norm"], model["rms_eps"])
    z_mtp = None
    if model["num_nextn_predict_layers"]:
        mtp, eps = p["mtp"], model["rms_eps"]
        nxt = p["token_emb"][jnp.roll(ids, -1, axis=1)]
        both = jnp.concatenate([_rms_norm(nxt, mtp["enorm"], eps),
                                _rms_norm(z, mtp["hnorm"], eps)], -1)
        x = run(mtp["block"], jnp.dot(both, mtp["proj"]["kernel"]), False)
        z_mtp = _rms_norm(x, mtp["final_norm"], eps)
    return (z, z_mtp, kept) if keep_m else (z, z_mtp)


def _nll(z, head, targets):
    """(B, T) next-token negative log-likelihoods of states z (B, T, D)
    against targets (B, T), the rows a chunk at a time."""
    b, t, d = z.shape
    rows = _block_size(b * t, HEAD_CHUNK)

    @jax.checkpoint
    def chunk(args):
        h, target = args
        logp = jax.nn.log_softmax(jnp.dot(h, head), -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    return jax.lax.map(chunk, (z.reshape(-1, rows, d),
                               targets.reshape(-1, rows))).reshape(b, t)


def chosen_experts(params, text, image, model: Mapping[str, Any]):
    """(expert layers, B, T, k): the experts every token chooses in every
    expert layer, the prediction module's last, in float32."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        with jax.default_matmul_precision("highest"):
            *_, kept = _states(p, _ids(text, image, model), model, False,
                               None, keep_m=True)
            return jnp.stack([route(m, ff, model)[0] for m, ff in kept])
    return jax.jit(run)(params, text, image)


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False, chosen=None):
    """``loss_main + mtp_loss_weight * loss_mtp``; returns ``(loss,
    (loss_main, loss_mtp))``. ``chosen``: (expert layers, B, T, k) expert
    ids to route by (module docstring, near-ties); None: the reference's
    own."""
    p = params["params"]
    ids = _ids(text, image, model)
    z, z_mtp = _states(p, ids, model, checkpoint_blocks, chosen)
    # position i is scored against token i + 1 (the last against nothing)
    main = _nll(z, p["lm_head"], jnp.roll(ids, -1, axis=1))[:, :-1].mean()
    if z_mtp is None:
        return main, (main, jnp.zeros(()))
    mtp = _nll(z_mtp, p["lm_head"],
               jnp.roll(ids, -2, axis=1))[:, :-2].mean()
    return main + model["mtp_loss_weight"] * mtp, (main, mtp)


def _loss_and_grads(params, text, image, model, checkpoint_blocks, chosen):
    def run(params, text, image, chosen):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            (loss, _), grads = jax.value_and_grad(
                lambda q: loss_fn(q, text, image, model, checkpoint_blocks,
                                  chosen), has_aux=True)(params)
        return loss, grads
    return jax.jit(run)(params, text, image, chosen)


def loss_and_grads(params, text, image, model: Mapping[str, Any],
                   checkpoint_blocks: bool = False):
    """Loss and gradients of the mean over the sequences of ``text`` /
    ``image``: all of them through one jitted call."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           None)


def loss_and_grads_at(chosen, params, text, image, model: Mapping[str, Any],
                      checkpoint_blocks: bool = False):
    """:func:`loss_and_grads` at the expert sets ``chosen`` (expert
    layers, B, T, k) instead of the reference's own."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           jnp.asarray(chosen))


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def attention_pairs(model: Mapping[str, Any]) -> int:
    """Allowed (query, key) pairs of one head of one sequence."""
    t = tokens_per_sample(model)
    return t * (t + 1) // 2


def attention_flops_forward(model: Mapping[str, Any]) -> int:
    """The scores (``nope + rope`` wide) and the values (``v`` wide) of one
    sequence in one layer, all heads, allowed pairs only."""
    wide = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] \
        + model["v_head_dim"]
    return 2 * attention_pairs(model) * wide * model["num_heads"]


def attention_matmul_params(model: Mapping[str, Any]) -> int:
    """The five projections of one layer."""
    d, h = model["hidden_size"], model["num_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"]
            + model["q_lora_rank"] * h * (nope + rope)
            + d * (model["kv_lora_rank"] + rope)
            + model["kv_lora_rank"] * h * (nope + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def held_assignments_per_token(model: Mapping[str, Any]) -> float:
    """Assignments a token makes to experts held here, in expectation
    under a router that favours none."""
    return (model["experts_per_token"] * model["experts_held"]
            / model["num_experts"])


def expert_layer_ff_params(model: Mapping[str, Any]) -> float:
    """Feed-forward weights one token is multiplied by in one expert
    layer: the router, the shared expert, and the held experts it is routed
    to (in expectation)."""
    expert = 3 * model["hidden_size"] * model["expert_width"]
    return (model["hidden_size"] * model["num_experts"]
            + (model["num_shared_experts"]
               + held_assignments_per_token(model)) * expert)


def forward_flops_per_token(model: Mapping[str, Any]) -> float:
    """Required operations of one token's forward pass (a sequence's over
    its tokens): every block's five projections and its attention over the
    causal pairs, the dense block, the expert layers' router, shared expert
    and held assignments, the head over the predicted positions of both
    losses, the prediction module's ``W_eh``."""
    t = tokens_per_sample(model)
    n, mtp = blocks(model), model["num_nextn_predict_layers"]
    d = model["hidden_size"]
    products = (n * attention_matmul_params(model)
                + model["num_dense_layers"] * 3 * d * model["dense_width"]
                + expert_layers(model) * expert_layer_ff_params(model)
                + mtp * 2 * d * d)
    head = 2.0 * d * model["vocab_size"] * ((t - 1) + mtp * (t - 2))
    return 2.0 * products + (n * attention_flops_forward(model) + head) / t


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only (contract: ``yardsticks/dalle.py``)."""
    return 3.0 * tokens_per_sample(model) * forward_flops_per_token(model)


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
    sample's forward and backward pass, every block. Forward reads a
    head's ``nope + rope`` query lanes, ``nope`` key lanes and ``v`` value
    lanes and writes ``v`` context lanes, all heads, and reads the ONE
    rotary key (T x rope: once, not once a head); backward reads those, the
    context and its cotangent and writes every cotangent, at twice the
    flops."""
    t, h = tokens_per_sample(model), model["num_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    value = model["v_head_dim"]
    lane = t * act_bytes
    forward = lane * (h * (2 * nope + rope + 2 * value) + rope)
    backward = lane * (h * (4 * nope + 2 * rope + 4 * value) + 2 * rope)
    flops = attention_flops_forward(model)
    return _least([(flops, forward), (2 * flops, backward)] * blocks(model),
                  peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: three products an
    expert layer (the prediction module's among them) over the assignments
    the held experts receive in expectation (the shared expert and the
    dense block are no grouped products and are not counted). Bytes are the
    rows in and out."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 3 * dim * width * rows
    nbytes = rows * (2 * dim + 3 * width) * act_bytes
    calls = [(flops, nbytes), (2 * flops, 2 * nbytes)] * expert_layers(model)
    return _least(calls, peaks)
