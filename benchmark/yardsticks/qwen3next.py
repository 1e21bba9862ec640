"""The ``qwen3next`` yardstick: what the benchmark knows about the
architecture of ``model_type`` ``qwen3_next`` as Qwen3-Next-80B-A3B-Instruct
states it (Qwen; config.json at
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct) — the plain
reference that decides ``correct``, and the counts behind ``mfu_pct``,
``attn_roofline``, ``moe_experts_roofline`` and ``gdn_rule_roofline``.
Contract: the docstring of ``yardsticks/dalle.py``. The prediction module
the model's description speaks of ("MTP 1") has no key in config.json and is
no part of this.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls; no
product has a bias: ``attention_bias`` false):

    every layer:  h = x + mixer(rmsnorm(x; attn_norm))
                  x' = h + moe(rmsnorm(h; ff_norm))
                  layer i is full attention where (i + 1) %
                  full_attention_interval == 0, else a gated-delta mixer
    gated_delta (parameters under ``gdn``; G query/key heads of dk, H value
    heads of dv, value head h reading query/key head h // (H / G); K taps):
      [q ; k ; v], z, [b ; a] = a . W_qkv, a . W_z, a . W_ba
                                             hidden -> 2 G dk + H dv, H dv,
                                             2 H
      [q ; k ; v] <- silu(sum_{j<K} taps[j] * [q ; k ; v]_{t-(K-1)+j})
                                             depthwise, causal, noughts
                                             before t = 0, no bias
      q^ = q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k^ = k / sqrt(sum k^2 + 1e-6)
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
      S_t = e^{g_t} S_{t-1} + k^_t (beta_t (v_t - (e^{g_t} S_{t-1})^T k^_t))^T
                                             S: (dk, dv) a value head,
                                             S_{-1} = 0
      o_t = S_t^T q^_t
      y = rmsnorm over each head's dv lanes of o (one scale vector ``norm``
          of dv), THEN times silu(z)         the norm before the gate
      mixer = y . W_out
    full_rope (parameters under ``attn``): q, g = a.W_q, a.W_gate (H x d
      each: the source's one q_proj, split), k, v = a.W_k, a.W_v (G x d)
      q, k <- rmsnorm over each head's d lanes (scales q_norm, k_norm)
      q, k <- rotary (rotate-half) on a head's FIRST d x
              partial_rotary_factor lanes, frequencies of a head of that
              many lanes, position = index; the other lanes as they are
      s_ij = q_i . k_j / sqrt(d), j <= i; query head h reads key-value head
      h // (H / G); mixer = (softmax(s) v * sigmoid(g)) . W_o
    moe:  p = softmax(m . W_r) over all num_experts; S = the k largest;
          w_e = p_e / sum_S p          (norm_topk_prob: the softmax over
                                       the chosen scores)
          moe = sum_{e in S, e held} w_e . expert_e(m)
                + sigmoid(m . w_g) shared(m)
          expert_e, shared: W_down(silu(W_gate m) * (W_up m)), widths
          expert_width and shared_expert_width

then a final RMSNorm, an untied head and the mean next-token cross-entropy
over the T - 1 predicted positions of ``[text || image + vocab_text]``.

**The recurrence is written as the recurrence**: a ``lax.scan`` over the
tokens that carries S, one token a step (:func:`delta_recurrence`),
independent of any chunked form and of any triangular inverse. What keeps
its backward pass inside one chip's memory at 8 192 tokens changes no
arithmetic: an outer scan over blocks of ``SCAN_BLOCK`` tokens carries S
under ``jax.checkpoint`` with the token scan inside; query rows go through
attention in blocks, the head's rows in chunks, token rows through the
shared expert in chunks, the held experts one at a time, each under
``jax.checkpoint``, and with ``checkpoint_blocks`` every layer is too.

**Departures from the published description, each as the configuration
file states it:** ``experts_held`` of the ``num_experts`` routed experts
are held (from ``expert_offset``; ``whole_layer_experts`` gives the uncut
layer for the test that adds the shares up, the gated shared expert counted
once); ``vocab_size`` is a slice; ``num_hidden_layers`` 4 stands for 48
(published layers 0-3, ``layer_kinds``); a norm's scale is held as ``s = 1
+ w`` (the source multiplies by ``1 + w`` with ``w`` from zeros: the same
function); the way in is three leaves ``in_proj/{qkv,z,ba}`` where the
source has ``in_proj_qkvz`` and ``in_proj_ba`` with an interleaved column
order (a layout: the same numbers permuted and split); the taps are
a leaf ``taps`` (K, lanes), a tap a row, where the source keeps a ``Conv1d``
weight (lanes, 1, K); ``q_proj`` is two leaves ``q`` and ``gate``; the
sequence reaches the model as the trainer's two fields. ``embed_init_std``
and the initial ``dt_bias`` / ``A_log`` are the program's initialisers, not
part of these equations.

**Near-ties**, as in ``yardsticks/trinity.py``: ``loss_and_grads_at``
evaluates the reference at given sets (``probes/qwen3next_precision.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

MASK_FILL = -1e30
QUERY_BLOCK = 256
HEAD_CHUNK = 2048
TOKEN_CHUNK = 4096
SCAN_BLOCK = 128


def layer_kinds(model: Mapping[str, Any]):
    kinds = model["layer_kinds"]
    return [kinds[i % len(kinds)] for i in range(model["num_hidden_layers"])]


def expert_layers(model: Mapping[str, Any]) -> int:
    """Every layer's second part is the expert block."""
    return model["num_hidden_layers"]


def gdn_layers(model: Mapping[str, Any]) -> int:
    return layer_kinds(model).count("gated_delta")


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _block_size(t: int, want: int) -> int:
    return max(b for b in range(1, min(t, want) + 1) if t % b == 0)


def partial_rotary(x, theta: float, turned: int):
    """x: (B, T, heads, d). Rotate-half over a head's first ``turned``
    lanes, with the frequencies of a head of ``turned`` lanes; the other d -
    ``turned`` lanes as they are. Position = index."""
    half = turned // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    first, rest = x[..., :turned], x[..., turned:]
    rot = jnp.concatenate([-first[..., half:], first[..., :half]], -1)
    return jnp.concatenate(
        [first * jnp.cos(ang) + rot * jnp.sin(ang), rest], -1)


def _attention(q, k, v):
    """q: (B, T, G, n, d) — n query heads to each of G key-value heads;
    k, v: (B, T, G, d). Dense masks, query rows a block at a time."""
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


def output_gate(ctx, a, attn):
    """The context times ``sigmoid(a . W_gate)``, a lane."""
    return ctx * jax.nn.sigmoid(jnp.dot(a, attn["gate"]["kernel"]))


def attention(a, attn, model: Mapping[str, Any]):
    b, t, _ = a.shape
    g, d, eps = model["num_kv_heads"], model["head_dim"], model["rms_eps"]
    n = model["num_heads"] // g
    q = jnp.dot(a, attn["q"]["kernel"]).reshape(b, t, g * n, d)
    k = jnp.dot(a, attn["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, attn["v"]["kernel"]).reshape(b, t, g, d)
    q, k = _rms_norm(q, attn["q_norm"], eps), _rms_norm(k, attn["k_norm"], eps)
    turned = int(d * model["partial_rotary_factor"])
    q = partial_rotary(q, model["rope_theta"], turned)
    k = partial_rotary(k, model["rope_theta"], turned)
    ctx = _attention(q.reshape(b, t, g, n, d), k, v)
    return jnp.dot(output_gate(ctx, a, attn), attn["out"]["kernel"])


def delta_recurrence(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` with ``S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t -
    (e^{g_t} S_{t-1})^T k_t))^T`` and ``S_{-1}`` = 0, token by token. q, k:
    (B, T, G, dk), normalised by the caller; v: (B, T, G, R, dv), the value
    heads as G groups of R; g, beta: (B, T, G, R). Returns o like v. An
    outer scan over blocks of tokens carries S under ``jax.checkpoint``
    (module docstring). Elementwise products and sums, no ``dot``: a
    control that rounds the operands of products (``probes/
    qwen3next_precision.py``) leaves the recurrence float32, as the program
    keeps its states."""
    b, t = v.shape[:2]
    rows = _block_size(t, SCAN_BLOCK)

    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        along_keys = lambda x: x[:, :, None, :, None]     # (B, G, 1, dk, 1)
        s = jnp.exp(g_t)[..., None, None] * s
        read = jnp.sum(s * along_keys(k_t), -2)
        s = s + along_keys(k_t) \
            * (beta_t[..., None] * (v_t - read))[..., None, :]
        return s, jnp.sum(s * along_keys(q_t), -2)

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    by_time = lambda x: x.swapaxes(0, 1).reshape(
        t // rows, rows, b, *x.shape[2:])
    start = jnp.zeros((b, *v.shape[2:4], q.shape[-1], v.shape[-1]), v.dtype)
    _, o = jax.lax.scan(block, start,
                        tuple(map(by_time, (q, k, v, g, beta))))
    return o.reshape(t, b, *v.shape[2:]).swapaxes(0, 1)


def causal_taps(x, taps):
    """``silu(sum_j taps[j] x_{t - (K - 1) + j})``, the sum written out over
    the taps on a sequence padded with K - 1 noughts in front; no bias."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + t] for j in range(k)))


def l2_normed(x):
    """Over the last axis: ``x / sqrt(sum x^2 + 1e-6)``."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def decay_and_beta(b_cols, a_cols, gdn):
    """``(g, beta)``: the log of a token's decay a value head, ``-exp(A_log)
    softplus(a + dt_bias)``, and the rule's gate ``sigmoid(b)``."""
    return (-jnp.exp(gdn["A_log"]) * jax.nn.softplus(a_cols + gdn["dt_bias"]),
            jax.nn.sigmoid(b_cols))


def normed_then_gated(o, z, scale, eps: float):
    """The norm first, the gate after: RMS norm over each head's lanes of
    ``o`` (B, T, H, dv) with one scale vector (dv,), then times silu(z)."""
    return _rms_norm(o, scale, eps) * jax.nn.silu(z)


def gated_delta(a, gdn, model: Mapping[str, Any]):
    """The gated-delta-rule mixer. gdn: "in_proj" -> {"qkv", "z", "ba"} ->
    {"kernel"}, "out_proj" -> {"kernel"}, "taps" (K, 2 G dk + H dv),
    "dt_bias", "A_log" (H each), "norm" (dv)."""
    b, t, _ = a.shape
    g, h = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    r, keys, inner = h // g, g * dk, h * dv
    way_in = gdn["in_proj"]
    qkv = jnp.dot(a, way_in["qkv"]["kernel"])
    z = jnp.dot(a, way_in["z"]["kernel"])
    b_cols, a_cols = jnp.split(jnp.dot(a, way_in["ba"]["kernel"]), [h],
                               axis=-1)
    q, k, v = jnp.split(causal_taps(qkv, gdn["taps"]), [keys, 2 * keys],
                        axis=-1)
    log_decay, beta = decay_and_beta(b_cols, a_cols, gdn)
    o = delta_recurrence(
        l2_normed(q.reshape(b, t, g, dk)) * dk ** -0.5,
        l2_normed(k.reshape(b, t, g, dk)), v.reshape(b, t, g, r, dv),
        log_decay.reshape(b, t, g, r), beta.reshape(b, t, g, r))
    y = normed_then_gated(o.reshape(b, t, h, dv), z.reshape(b, t, h, dv),
                          gdn["norm"], model["rms_eps"])
    return jnp.dot(y.reshape(b, t, inner), gdn["out_proj"]["kernel"])


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


def shared_gate(m, ff):
    """``sigmoid(m . w_g)``, a number a token."""
    return jax.nn.sigmoid(jnp.dot(m, ff["shared_gate"]))[..., None]


def shared_part(m, ff):
    """What every token takes beside its routed experts: the shared expert
    times its gate."""
    return shared_gate(m, ff) * gated_block(m, ff["shared"])


def route(m, ff, model: Mapping[str, Any], chosen=None):
    """The k experts of every token and their weights: (ids, weights), each
    (..., k). The softmax over all the router's scores, its k largest (with
    ``chosen`` (..., k) those ids stand for them), renormalised over the
    chosen (``norm_topk_prob``)."""
    probs = jax.nn.softmax(jnp.dot(m, ff["router"]), -1)
    if chosen is None:
        _, chosen = jax.lax.top_k(probs, model["experts_per_token"])
    top = jnp.take_along_axis(probs, chosen, -1)
    return chosen, top / jnp.sum(top, -1, keepdims=True)


def expert_sum(m, idx, p, experts, first: int):
    """sum over the experts of ``experts`` (leaves stacked on the leading
    axis; the first is expert ``first`` of the router's) of routing weight
    x expert(m); a token not routed to an expert weighs 0 there. Each
    expert's products are computed again in the backward pass."""
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
    router's, the gated shared expert is added once."""
    idx, p = route(m, ff, model)
    return expert_sum(m, idx, p, ff["experts"], 0) + shared_part(m, ff)


def _layer(p, x, kind: str, model: Mapping[str, Any], chosen=None):
    """Both parts of a layer; returns it and the expert block's input."""
    a = _rms_norm(x, p["attn_norm"], model["rms_eps"])
    if kind == "gated_delta":
        h = x + gated_delta(a, p["gdn"], model)
    elif kind == "full_rope":
        h = x + attention(a, p["attn"], model)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    m = _rms_norm(h, p["ff_norm"], model["rms_eps"])
    idx, weights = route(m, p["ff"], model, chosen)
    f = expert_sum(m, idx, weights, p["ff"]["experts"],
                   model["expert_offset"]) + shared_part(m, p["ff"])
    return h + f, m


def _embed(p, text, image, model: Mapping[str, Any]):
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    return ids, p["token_emb"][ids]


def chosen_experts(params, text, image, model: Mapping[str, Any]):
    """(layers, B, T, k): the experts every token chooses in every layer, in
    float32 (what ``probes/qwen3next_precision.py`` sets the program's
    bfloat16 choices against: near-ties flip)."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        chosen = []
        with jax.default_matmul_precision("highest"):
            _, x = _embed(p, text, image, model)
            for i, kind in enumerate(layer_kinds(model)):
                lp = p[f"layer_{i}"]
                x, m = _layer(lp, x, kind, model)
                chosen.append(route(m, lp["ff"], model)[0])
        return jnp.stack(chosen)
    return jax.jit(run)(params, text, image)


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False, chosen=None):
    """Mean next-token cross-entropy over the T - 1 predicted positions;
    returns ``(loss, (loss_text, loss_img))``, the means over the targets
    of the two fields. ``chosen``: (layers, B, T, k) expert ids to route by
    (module docstring, near-ties); None: the reference's own."""
    p = params["params"]
    ids, x = _embed(p, text, image, model)
    for i, kind in enumerate(layer_kinds(model)):
        layer = lambda lp, x, sets, kind=kind: _layer(
            lp, x, kind, model, sets)[0]
        x = (jax.checkpoint(layer) if checkpoint_blocks else layer)(
            p[f"layer_{i}"], x, None if chosen is None else chosen[i])
    x = _rms_norm(x, p["final_norm"], model["rms_eps"])

    head = p["lm_head"]
    b, t = ids.shape
    rows = _block_size(b * (t - 1), HEAD_CHUNK)

    @jax.checkpoint
    def chunk(args):
        h, target = args
        logp = jax.nn.log_softmax(jnp.dot(h, head), -1)
        return -jnp.take_along_axis(logp, target[:, None], -1)[:, 0]

    nll = jax.lax.map(chunk, (
        x[:, :-1].reshape(-1, rows, x.shape[-1]),
        ids[:, 1:].reshape(-1, rows))).reshape(b, t - 1)
    n_text = text.shape[1] - 1        # targets 1 .. text_len - 1
    return nll.mean(), (nll[:, :n_text].mean(), nll[:, n_text:].mean())


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
    """:func:`loss_and_grads` at the expert sets ``chosen`` (layers, B, T,
    k) instead of the reference's own."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           jnp.asarray(chosen))


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def attention_pairs(model: Mapping[str, Any], kind: str) -> int:
    """Allowed (query, key) pairs of one head of one sequence; none in a
    layer whose mixer is no attention."""
    if kind != "full_rope":
        return 0
    t = tokens_per_sample(model)
    return t * (t + 1) // 2


def attention_flops_forward(model: Mapping[str, Any], kind: str) -> int:
    """QK^T and PV of one sequence, all query heads, allowed pairs only."""
    return (4 * attention_pairs(model, kind) * model["head_dim"]
            * model["num_heads"])


def held_assignments_per_token(model: Mapping[str, Any]) -> float:
    """Assignments a token makes to experts held here, in expectation
    under a router that favours none."""
    return (model["experts_per_token"] * model["experts_held"]
            / model["num_experts"])


def attention_matmul_params(model: Mapping[str, Any]) -> int:
    """q, gate and out (hidden x H d each), k and v (hidden x G d)."""
    return model["hidden_size"] * model["head_dim"] * (
        3 * model["num_heads"] + 2 * model["num_kv_heads"])


def gdn_key_lanes(model: Mapping[str, Any]) -> int:
    return model["linear_num_key_heads"] * model["linear_key_head_dim"]


def gdn_value_lanes(model: Mapping[str, Any]) -> int:
    return model["linear_num_value_heads"] * model["linear_value_head_dim"]


def gdn_conv_lanes(model: Mapping[str, Any]) -> int:
    """q, k and v side by side: what the taps run over."""
    return 2 * gdn_key_lanes(model) + gdn_value_lanes(model)


def gdn_matmul_params(model: Mapping[str, Any]) -> int:
    """in_proj's three (hidden x (2 G dk + H dv), x H dv, x 2 H) and
    out_proj (H dv x hidden)."""
    inner = gdn_value_lanes(model)
    return model["hidden_size"] * (
        gdn_conv_lanes(model) + inner + 2 * model["linear_num_value_heads"]
        + inner)


def gdn_rule_flops_forward(model: Mapping[str, Any]) -> int:
    """The recurrence's own multiply-adds of one token, whatever computes
    them: a value head's state is read back (``S^T k``), written (``k
    u^T``) and read out (``S^T q``), 2 dk dv each, and the K taps a lane.
    What a chunked form adds (the products inside a chunk, the triangular
    inverse) is its implementation's and not counted."""
    return (6 * model["linear_num_value_heads"]
            * model["linear_key_head_dim"] * model["linear_value_head_dim"]
            + 2 * model["linear_conv_kernel_dim"] * gdn_conv_lanes(model))


def shared_width(model: Mapping[str, Any]) -> int:
    """The shared expert's width: ``num_shared_experts`` of the experts'
    (the source's ``shared_expert_intermediate_size``)."""
    return model["num_shared_experts"] * model["expert_width"]


def expert_layer_matmul_params(model: Mapping[str, Any]) -> float:
    """Weights one token is multiplied by in an expert block: the router,
    the shared expert and its gate, and the held experts it is routed to
    (in expectation); three products an expert."""
    return (model["hidden_size"] * model["num_experts"]
            + 3 * model["hidden_size"] * shared_width(model)
            + model["hidden_size"]
            + held_assignments_per_token(model) * 3 * model["hidden_size"]
            * model["expert_width"])


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only — each layer's mixer (a gated-delta mixer's two projections and
    the recurrence's own multiply-adds; attention's five projections and
    its causal pairs) and its expert block (the router, the gated shared
    expert and the held experts' products for the assignments they receive
    in expectation), the untied head over the predicted positions."""
    t = tokens_per_sample(model)
    fwd = 0.0
    for kind in layer_kinds(model):
        if kind == "gated_delta":
            fwd += t * (2.0 * gdn_matmul_params(model)
                        + gdn_rule_flops_forward(model))
        else:
            fwd += 2.0 * t * attention_matmul_params(model) \
                + attention_flops_forward(model, kind)
        fwd += 2.0 * t * expert_layer_matmul_params(model)
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
    sample's forward and backward pass, the attention layers only. Forward
    reads q and writes the context (T x H x d each) and reads k, v (T x G
    x d each); backward reads q, context, its cotangent, k, v and writes
    dq, dk, dv, at twice the flops."""
    t, d = tokens_per_sample(model), model["head_dim"]
    wide = t * model["num_heads"] * d * act_bytes
    narrow = t * model["num_kv_heads"] * d * act_bytes
    calls = []
    for kind in layer_kinds(model):
        if kind == "full_rope":
            flops = attention_flops_forward(model, kind)
            calls += [(flops, 2 * wide + 2 * narrow),
                      (2 * flops, 4 * wide + 4 * narrow)]
    return _least(calls, peaks)


def gdn_rule_min_seconds_per_sample(model: Mapping[str, Any],
                                    peaks: Mapping[str, float],
                                    act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the taps and the rule
    (``gdn/conv`` and ``gdn/rule``, nothing of the projections, the norm or
    the gate) of one sample's forward and backward pass, whatever
    implements them and whatever its chunk: a mixer layer's forward does
    the recurrence's own multiply-adds (:func:`gdn_rule_flops_forward`),
    reads ``q``, ``k``, ``v``, ``b`` and ``a`` and writes ``o`` once; its
    backward reads those and ``o``'s cotangent and writes their
    cotangents, at twice the flops. The parameters (taps, ``dt_bias``,
    ``A_log``) are a few thousand numbers and not counted. A chunked form's
    extra products, its triangular inverse and a replay under
    rematerialisation are the program's choice and not counted, as in
    ``train_flops_per_sample``."""
    t = tokens_per_sample(model)
    qkv = t * gdn_conv_lanes(model) * act_bytes
    ba = t * 2 * model["linear_num_value_heads"] * act_bytes
    o = t * gdn_value_lanes(model) * act_bytes
    flops = t * gdn_rule_flops_forward(model)
    calls = [(flops, qkv + ba + o),
             (2 * flops, 2 * (qkv + ba) + o)] * gdn_layers(model)
    return _least(calls, peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: three products an
    expert block and direction over the assignments the held experts
    receive in expectation (the shared expert is no grouped product and is
    not counted). Bytes are the rows in and out (the weights are read once
    for all the samples of a step's micro-batch)."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 3 * dim * width * rows
    nbytes = rows * (2 * dim + 3 * width) * act_bytes
    calls = [(flops, nbytes), (2 * flops, 2 * nbytes)] * expert_layers(model)
    return _least(calls, peaks)
