"""The ``nemotronh`` yardstick: what the benchmark knows about the
architecture of ``model_type`` ``nemotron_h`` as the 52-layer stack of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 states it (nvidia; config.json at
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16) —
the plain reference that decides ``correct``, and the counts behind
``mfu_pct``, ``attn_roofline``, ``moe_experts_roofline`` and
``ssm_scan_roofline``. Contract: the docstring of ``yardsticks/dalle.py``.
The denoiser tower the model's description speaks of has no key in
config.json and is no part of this.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls;
``config.json`` pins the sizes, ``hybrid_override_pattern``, ``conv_kernel``,
``use_conv_bias``, ``n_groups``, ``ssm_state_size``, ``mamba_num_heads``,
``mamba_head_dim``, ``mlp_hidden_act``, ``norm_topk_prob``,
``routed_scaling_factor``, ``moe_shared_expert_intermediate_size``; the rest
is the family's public modeling code as the configuration file's
``assumed`` states it):

    every layer:  x' = x + part(rmsnorm(x; norm))    ONE part, ONE norm
    mamba2:   [z ; xBC ; dt] = a . W_in     hidden -> H P + (H P + 2 G N) + H
              xBC <- silu(sum_{j<K} taps[j] * xBC_{t-(K-1)+j} + conv_bias)
                                            depthwise, causal, noughts
                                            before t = 0
              x (T, H, P), B (T, G, N), C (T, G, N) = split(xBC); head h
                                            reads group h // (H / G)
              D_t,h = softplus(dt_t,h + dt_bias_h);  A_h = -exp(A_log_h)
              S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T       S_{-1} = 0
              y_t = S_t C_t + D_h x_t       S: (P, N) a head
              y <- rmsnorm over each group's H P / G lanes of (y * silu(z))
                                            one scale vector ``norm``
              part = y . W_out
    full_nope: q, k, v = a.W_q (H x d), a.W_k (G x d), a.W_v (G x d)
              s_ij = q_i.k_j / sqrt(d), j <= i; query head h reads
              key-value head h // (H / G); part = softmax(s) v . W_o
                                            no positions, no norms, no gate
    experts:  s = sigmoid(m . W_r)          num_experts; m is the layer's
                                            one normed input
              S = the k largest of s + b    b: router_bias, zeros, no
                                            gradient reaches it
              p_e = route_scale * s_e / (sum_S s + 1e-20)
              part = shared(m) + sum_{e in S, e held} p_e . expert_e(m)
              shared, expert_e: W_down relu(W_up m)^2   two products, NOT
                                            gated; widths
                                            shared_expert_width and
                                            expert_width

then a final RMSNorm, an untied head and the mean next-token cross-entropy
over the T - 1 predicted positions of ``[text || image + vocab_text]``.

**The recurrence is written as the recurrence**: a ``lax.scan`` over the
tokens that carries S, one token a step (:func:`recurrence`), independent
of any chunked form. What keeps its backward pass inside one chip's memory
at 8 192 tokens changes no arithmetic: an outer scan over blocks of
``SCAN_BLOCK`` tokens carries S under ``jax.checkpoint`` with the token
scan inside (the states kept are one a block, not one a token); query rows
go through attention in blocks, the head's rows in chunks, token rows
through the shared expert in chunks, the held experts one at a time, each
under ``jax.checkpoint``, and with ``checkpoint_blocks`` every layer is
too.

**Departures from the published description, each as the configuration
file states it:** ``experts_held`` of the ``num_experts`` routed experts
are held (from ``expert_offset``; ``whole_layer_experts`` gives the uncut
layer for the test that adds the shares up, the shared expert counted
once); ``vocab_size`` is a slice; ``num_hidden_layers`` 7 stands for 52
(published layers 0-6, ``layer_kinds``); the taps are a leaf ``taps`` (K,
H P + 2 G N), a tap a row, where the source keeps a ``Conv1d`` weight
(lanes, 1, K); the bias b is never updated; the normaliser's epsilon is
the program's 1e-20; the sequence reaches the model as the trainer's two
fields. ``embed_init_std`` and the initial ``dt_bias`` / ``A_log`` are the
program's initialisers, not part of these equations.

**Near-ties**, as in ``yardsticks/trinity.py``: ``loss_and_grads_at``
evaluates the reference at given sets (``probes/nemotronh_precision.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

MASK_FILL = -1e30
QUERY_BLOCK = 256
HEAD_CHUNK = 2048
TOKEN_CHUNK = 4096
SCAN_BLOCK = 128


def layer_kinds(model: Mapping[str, Any]):
    kinds = model["layer_kinds"]
    return [kinds[i % len(kinds)] for i in range(model["num_hidden_layers"])]


def expert_layers(model: Mapping[str, Any]) -> int:
    return layer_kinds(model).count("experts")


def mamba_layers(model: Mapping[str, Any]) -> int:
    return layer_kinds(model).count("mamba2")


def _rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _block_size(t: int, want: int) -> int:
    return max(b for b in range(1, min(t, want) + 1) if t % b == 0)


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


def attention(a, attn, model: Mapping[str, Any]):
    b, t, _ = a.shape
    g, d = model["num_kv_heads"], model["head_dim"]
    n = model["num_heads"] // g
    q = jnp.dot(a, attn["q"]["kernel"]).reshape(b, t, g, n, d)
    k = jnp.dot(a, attn["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, attn["v"]["kernel"]).reshape(b, t, g, d)
    return jnp.dot(_attention(q, k, v), attn["out"]["kernel"])


def recurrence(x, bm, cm, dt, a, d):
    """``y_t = S_t C_t + d x_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T`` and ``S_{-1}`` = 0, token by token. x: (B, T, G, R, P), the
    heads as G groups of R; bm, cm: (B, T, G, N); dt: (B, T, G, R); a, d:
    (G, R). Returns y like x. An outer scan over blocks of tokens carries S
    under ``jax.checkpoint`` (module docstring)."""
    b, t = x.shape[:2]
    rows = _block_size(t, SCAN_BLOCK)

    def token(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        y_t = jnp.sum(s * c_t[:, :, None, None, :], -1) + d[..., None] * x_t
        return s, y_t

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    by_time = lambda v: v.swapaxes(0, 1).reshape(
        t // rows, rows, b, *v.shape[2:])
    start = jnp.zeros((*x.shape[:1], *x.shape[2:], bm.shape[-1]), x.dtype)
    _, y = jax.lax.scan(block, start, tuple(map(by_time, (x, bm, cm, dt))))
    return y.reshape(t, *x.shape[:1], *x.shape[2:]).swapaxes(0, 1)


def causal_taps(xbc, taps, bias):
    """``silu(sum_j taps[j] xbc_{t - (K - 1) + j} + bias)``, the sum written
    out over the taps on a sequence padded with K - 1 noughts in front."""
    k, t = taps.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + t] for j in range(k))
                       + bias)


def gated_norm(y, z, scale, groups: int, eps: float):
    """The gate first, the norm after: RMS norm over each of ``groups`` runs
    of lanes of ``y * silu(z)``, one scale vector for all of them."""
    gated = (y * jax.nn.silu(z)).reshape(*y.shape[:-1], groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return normed.reshape(y.shape) * scale


def mamba2(a, ssm, model: Mapping[str, Any]):
    """The state-space mixer. ssm: {"in_proj", "out_proj"} -> {"kernel"},
    "taps" (K, H P + 2 G N), "conv_bias", "dt_bias", "A_log", "D" (H each),
    "norm" (H P)."""
    b, t, _ = a.shape
    h, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n = model["ssm_groups"], model["ssm_state_size"]
    r, inner = h // g, h * p
    z, xbc, dt = jnp.split(jnp.dot(a, ssm["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = causal_taps(xbc, ssm["taps"], ssm["conv_bias"])
    x, bm, cm = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + ssm["dt_bias"])
    y = recurrence(x.reshape(b, t, g, r, p), bm.reshape(b, t, g, n),
                   cm.reshape(b, t, g, n), dt.reshape(b, t, g, r),
                   -jnp.exp(ssm["A_log"]).reshape(g, r),
                   ssm["D"].reshape(g, r))
    y = gated_norm(y.reshape(b, t, inner), z, ssm["norm"], g,
                   model["rms_eps"])
    return jnp.dot(y, ssm["out_proj"]["kernel"])


def relu2(u):
    """What stands between an expert's two products: the square of ReLU."""
    return jax.nn.relu(u) ** 2


def ungated_block(m, w):
    """``W_down relu(W_up m)^2`` on every token, the tokens a chunk at a
    time. w: {"up", "down"} -> {"kernel"}."""
    flat = m.reshape(-1, m.shape[-1])
    rows = _block_size(flat.shape[0], TOKEN_CHUNK)

    @jax.checkpoint
    def chunk(x):
        return jnp.dot(relu2(jnp.dot(x, w["up"]["kernel"])),
                       w["down"]["kernel"])

    return jax.lax.map(chunk, flat.reshape(-1, rows, flat.shape[-1])) \
        .reshape(m.shape)


def route(m, ff, model: Mapping[str, Any], chosen=None):
    """The k experts of every token and their weights: (ids, weights),
    each (..., k). Sigmoid scores in f32; the k largest of score + bias
    (with ``chosen`` (..., k) those ids stand for them); the weights are
    the chosen experts' scores without the bias, over their sum
    (``route_norm``), times ``route_scale``."""
    scores = jax.nn.sigmoid(jnp.dot(m, ff["router"]))
    if chosen is None:
        select = scores
        if model["selection_bias"]:
            select = scores + jax.lax.stop_gradient(ff["router_bias"])
        _, chosen = jax.lax.top_k(select, model["experts_per_token"])
    top = jnp.take_along_axis(scores, chosen, -1)
    if model["route_norm"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return chosen, top * model["route_scale"]


def expert_sum(m, idx, p, experts, first: int):
    """sum over the experts of ``experts`` (leaves ``up``, ``down`` stacked
    on the leading axis; the first is expert ``first`` of the router's) of
    routing weight x expert(m); a token not routed to an expert weighs 0
    there. Each expert's products are computed again in the backward
    pass."""
    @jax.checkpoint
    def one(y, xs):
        e, up, down = xs
        weight = jnp.sum(jnp.where(idx == e, p, 0.0), -1)
        out = jnp.dot(relu2(jnp.dot(m, up)), down)
        return y + weight[..., None] * out, None

    n = experts["up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (first + jnp.arange(n), experts["up"],
                         experts["down"]))
    return y


def whole_layer_experts(m, ff, model: Mapping[str, Any]):
    """The uncut expert layer: ``ff["experts"]`` holds all of the
    router's, the shared expert is added once."""
    idx, p = route(m, ff, model)
    return expert_sum(m, idx, p, ff["experts"], 0) \
        + ungated_block(m, ff["shared"])


def _layer(p, x, kind: str, model: Mapping[str, Any], chosen=None):
    """``x + part(rmsnorm(x))``; returns it and the normed input."""
    a = _rms_norm(x, p["norm"], model["rms_eps"])
    if kind == "mamba2":
        part = mamba2(a, p["ssm"], model)
    elif kind == "full_nope":
        part = attention(a, p["attn"], model)
    elif kind == "experts":
        idx, weights = route(a, p["ff"], model, chosen)
        part = expert_sum(a, idx, weights, p["ff"]["experts"],
                          model["expert_offset"]) \
            + ungated_block(a, p["ff"]["shared"])
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return x + part, a


def _embed(p, text, image, model: Mapping[str, Any]):
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    return ids, p["token_emb"][ids]


def chosen_experts(params, text, image, model: Mapping[str, Any]):
    """(expert layers, B, T, k): the experts every token chooses in every
    expert layer, in float32 (what ``probes/nemotronh_precision.py`` sets
    the program's bfloat16 choices against: near-ties flip)."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        chosen = []
        with jax.default_matmul_precision("highest"):
            _, x = _embed(p, text, image, model)
            for i, kind in enumerate(layer_kinds(model)):
                lp = p[f"layer_{i}"]
                x, a = _layer(lp, x, kind, model)
                if kind == "experts":
                    chosen.append(route(a, lp["ff"], model)[0])
        return jnp.stack(chosen)
    return jax.jit(run)(params, text, image)


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False, chosen=None):
    """Mean next-token cross-entropy over the T - 1 predicted positions;
    returns ``(loss, (loss_text, loss_img))``, the means over the targets
    of the two fields. ``chosen``: (expert layers, B, T, k) expert ids to
    route by (module docstring, near-ties); None: the reference's own."""
    p = params["params"]
    ids, x = _embed(p, text, image, model)
    seen = 0
    for i, kind in enumerate(layer_kinds(model)):
        layer = lambda lp, x, sets, kind=kind: _layer(
            lp, x, kind, model, sets)[0]
        sets = None
        if kind == "experts":
            sets = None if chosen is None else chosen[seen]
            seen += 1
        x = (jax.checkpoint(layer) if checkpoint_blocks else layer)(
            p[f"layer_{i}"], x, sets)
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
    """:func:`loss_and_grads` at the expert sets ``chosen`` (expert
    layers, B, T, k) instead of the reference's own."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           jnp.asarray(chosen))


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def attention_pairs(model: Mapping[str, Any], kind: str) -> int:
    """Allowed (query, key) pairs of one head of one sequence; none in a
    layer whose part is no attention."""
    if kind != "full_nope":
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
    """q and out (hidden x H d each), k and v (hidden x G d)."""
    return model["hidden_size"] * model["head_dim"] * 2 * (
        model["num_heads"] + model["num_kv_heads"])


def mamba_inner(model: Mapping[str, Any]) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def mamba_conv_lanes(model: Mapping[str, Any]) -> int:
    """x, B and C side by side: what the taps run over."""
    return mamba_inner(model) + 2 * model["ssm_groups"] \
        * model["ssm_state_size"]


def mamba_matmul_params(model: Mapping[str, Any]) -> int:
    """in_proj (hidden x (H P + lanes + H)) and out_proj (H P x hidden)."""
    inner = mamba_inner(model)
    return model["hidden_size"] * (
        inner + mamba_conv_lanes(model) + model["mamba_num_heads"] + inner)


def ssm_scan_flops_forward(model: Mapping[str, Any]) -> int:
    """The recurrence's own multiply-adds of one token, whatever computes
    them: the state's update and its read-out, 2 H P N each, and the K
    taps a lane. The products a chunked form adds (the masked form inside
    a chunk) are its implementation's and not counted."""
    return (4 * mamba_inner(model) * model["ssm_state_size"]
            + 2 * model["conv_kernel"] * mamba_conv_lanes(model))


def expert_layer_matmul_params(model: Mapping[str, Any]) -> float:
    """Weights one token is multiplied by in an expert layer: the router,
    the shared expert, and the held experts it is routed to (in
    expectation); two products an expert."""
    return (model["hidden_size"] * model["num_experts"]
            + 2 * model["hidden_size"] * model["shared_expert_width"]
            + held_assignments_per_token(model) * 2 * model["hidden_size"]
            * model["expert_width"])


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only — each layer's one part (the mixer's two projections and the
    recurrence's own multiply-adds; attention's four projections and its
    causal pairs; the router, the shared expert and the held experts'
    products for the assignments they receive in expectation), the untied
    head over the predicted positions."""
    t = tokens_per_sample(model)
    fwd = 0.0
    for kind in layer_kinds(model):
        if kind == "mamba2":
            fwd += t * (2.0 * mamba_matmul_params(model)
                        + ssm_scan_flops_forward(model))
        elif kind == "experts":
            fwd += 2.0 * t * expert_layer_matmul_params(model)
        else:
            fwd += 2.0 * t * attention_matmul_params(model) \
                + attention_flops_forward(model, kind)
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
        if kind == "full_nope":
            flops = attention_flops_forward(model, kind)
            calls += [(flops, 2 * wide + 2 * narrow),
                      (2 * flops, 4 * wide + 4 * narrow)]
    return _least(calls, peaks)


def ssm_scan_min_seconds_per_sample(model: Mapping[str, Any],
                                    peaks: Mapping[str, float],
                                    act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the taps and the recurrence
    (``ssm/conv`` and ``ssm/scan``, nothing of the projections, the gate or
    the norm) of one sample's forward and backward pass, whatever
    implements them and whatever its chunk: a mixer layer's forward does
    the recurrence's own multiply-adds (:func:`ssm_scan_flops_forward`),
    reads ``xBC`` and ``dt`` and writes ``y`` once; its backward reads
    those two and ``y``'s cotangent and writes the two cotangents, at twice
    the flops. The parameters (taps, bias, ``dt_bias``, ``A_log``, ``D``)
    are a few thousand numbers and not counted. A chunked form's extra
    products and a replay under rematerialisation are the program's choice
    and not counted, as in ``train_flops_per_sample``."""
    t = tokens_per_sample(model)
    xbc = t * mamba_conv_lanes(model) * act_bytes
    dt = t * model["mamba_num_heads"] * act_bytes
    y = t * mamba_inner(model) * act_bytes
    flops = t * ssm_scan_flops_forward(model)
    calls = [(flops, xbc + dt + y),
             (2 * flops, 2 * (xbc + dt) + y)] * mamba_layers(model)
    return _least(calls, peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: TWO products an
    expert layer and direction (the experts are not gated) over the
    assignments the held experts receive in expectation (the shared expert
    is no grouped product and is not counted). Bytes are the rows in and
    out (the weights are read once for all the samples of a step's
    micro-batch)."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 2 * dim * width * rows
    nbytes = rows * (2 * dim + 2 * width) * act_bytes
    calls = [(flops, nbytes), (2 * flops, 2 * nbytes)] * expert_layers(model)
    return _least(calls, peaks)
