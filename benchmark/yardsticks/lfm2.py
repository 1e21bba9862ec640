"""The ``lfm2`` yardstick: what the benchmark knows about the architecture
of LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``; config.json at
https://huggingface.co/LiquidAI/LFM2-8B-A1B) — the plain reference that
decides ``correct``, and the counts behind ``mfu_pct``, ``attn_roofline``,
``moe_experts_roofline`` and ``conv_mix_roofline``. Contract: the docstring
of ``yardsticks/dalle.py``.

**The equations** (x: (T, hidden), f32 throughout, ``highest`` matmuls;
``config.json`` pins the sizes, ``layer_types``, ``conv_L_cache``,
``conv_bias``, ``norm_topk_prob``, ``use_expert_bias``,
``routed_scaling_factor``; the rest is the family's public modeling code as
the configuration file's ``assumed`` states it):

    every layer:  h = x + operator(rmsnorm(x; attn_norm))
                  x' = h + feed_forward(rmsnorm(h; ff_norm))    two norms
    short_conv:   [B ; C ; u] = a . W_in           hidden -> 3 x hidden, no bias
                  z_t = sum_{j<K} w_j * (B * u)_{t-(K-1)+j}   depthwise, causal
                                                   (noughts before t = 0); * is
                                                   elementwise; K = conv_kernel
                  operator = (C * z) . W_out
    full_rope:    q, k, v = a.W_q (H x d), a.W_k (G x d), a.W_v (G x d)
                  q, k = rmsnorm(q; q_norm), rmsnorm(k; k_norm)   over a head's
                                                   d lanes, one vector each
                  q, k <- rotary (rotate-half over all d, position = index)
                  s_ij = q_i.k_j / sqrt(d), j <= i; query head h reads
                  key-value head h // (H / G);  operator = softmax(s) v . W_o
    dense layer (the leading num_dense_layers):
                  f = W_down(silu(W_gate m) * (W_up m))         dense_width
    expert layer: s = sigmoid(m . W_r)                          num_experts
                  S = the k largest of s + b       b: router_bias (the source's
                                                   expert_bias), zeros, no
                                                   gradient reaches it
                  p_e = route_scale * s_e / (sum_S s + 1e-20)
                  f = sum_{e in S, e held} p_e . expert_e(m)    no shared expert

then a final RMSNorm, the head = the embedding's table (``tied_embeddings``;
an ``lm_head`` leaf where a configuration unties it) and the mean next-token
cross-entropy over the T - 1 predicted positions of ``[text || image +
vocab_text]``.

**Departures from the published description, each as the configuration
file states it:**

- ``experts_held`` of the ``num_experts`` routed experts are held (from
  ``expert_offset``): the router scores all of them and the sum is over the
  held ones only. ``whole_layer_experts`` gives the uncut layer for the test
  that adds the shares up (no shared expert to count once).
- ``vocab_size`` is a slice of the published vocabulary: embedding, head
  and loss are over the slice.
- ``num_hidden_layers`` 5 and ``num_dense_layers`` 1 stand for the published
  24 and 2: the first dense layer (published layer 0, a convolution) and one
  period of expert layers (published layers 2-5: attention, then three
  convolutions); ``layer_kinds`` names all five.
- **the taps are a leaf ``taps`` of shape (K, hidden)**, a tap a row, where
  the source keeps a depthwise ``Conv1d`` weight (hidden, 1, K): the same
  numbers transposed, tap K - 1 on the token itself.
- **the bias b is never updated** (the source steps it outside the
  gradient); a leaf whose gradient is exactly zero here and in the program.
- **the normaliser's epsilon is 1e-20**, the program's for every sigmoid
  router; the family's code adds 1e-6 to a sum of four sigmoids (about 2):
  5e-7 of the weights, under what float32 resolves in the loss.
- what ``config.json`` has no key for is ``assumed``: ``tied_embeddings``,
  the head norms (``qk_norm``) and the rotary's form, the router's input
  (``router_input``: ``post_attention_norm``, the m the experts read), no
  attention bias.
- the sequence reaches the model as the trainer's two fields, ``text`` and
  ``image`` (ids offset by ``vocab_text``), concatenated.
- ``embed_init_std`` (assumed) is the program's initialiser, not part of
  these equations: the reference takes the parameters it is given.

**Near-ties**, as in ``yardsticks/trinity.py``: ``loss_and_grads_at``
evaluates the reference at given sets (``probes/lfm2_precision.py``).

What keeps the float32 reference inside one chip's memory at 8 192 tokens
changes no arithmetic: query rows go through attention in blocks, the
head's rows in chunks, token rows through the dense block in chunks, the
held experts one at a time, each under ``jax.checkpoint``, and with
``checkpoint_blocks`` every layer is too.
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


def layer_kinds(model: Mapping[str, Any]):
    kinds = model["layer_kinds"]
    return [kinds[i % len(kinds)] for i in range(model["num_hidden_layers"])]


def expert_layers(model: Mapping[str, Any]) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


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


def _attention(q, k, v, window):
    """q: (B, T, G, n, d) — n query heads to each of G key-value heads;
    k, v: (B, T, G, d). Dense masks, query rows a block at a time."""
    b, t, g, n, d = q.shape
    rows = _block_size(t, QUERY_BLOCK)
    cols = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qb, start = args
        i = start + jnp.arange(rows)
        allowed = cols[None, :] <= i[:, None]
        if window is not None:
            allowed &= i[:, None] - cols[None, :] < window
        s = jnp.einsum("bqgnd,bkgd->bgnqk", qb, k) * d ** -0.5
        w = jax.nn.softmax(jnp.where(allowed, s, MASK_FILL), -1)
        return jnp.einsum("bgnqk,bkgd->bqgnd", w, v)

    blocks = q.reshape(b, t // rows, rows, g, n, d).swapaxes(0, 1)
    out = jax.lax.map(block, (blocks, jnp.arange(t // rows) * rows))
    return out.swapaxes(0, 1).reshape(b, t, g * n * d)


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
    the chosen experts' scores without the bias, over their sum
    (``route_norm``), times ``route_scale``."""
    # the program's epsilon (module docstring: the family's is 1e-6)
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
    router's (there is no shared expert to count once)."""
    idx, p = route(m, ff, model)
    return expert_sum(m, idx, p, ff["experts"], 0)


def short_conv(a, conv):
    """The gated short convolution: ``(C * z) . W_out`` with ``[B ; C ; u]
    = a . W_in`` and ``z_t = sum_j taps[j] (B u)_{t - (K - 1) + j}``, the
    sum written out over the taps on a sequence padded with K - 1 noughts
    in front. conv: {"in_proj", "out_proj"} -> {"kernel"}, "taps" (K, D)."""
    gate_in, gate_out, u = jnp.split(jnp.dot(a, conv["in_proj"]["kernel"]),
                                     3, axis=-1)
    taps = conv["taps"]
    k, t = taps.shape[0], a.shape[1]
    padded = jnp.pad(gate_in * u, ((0, 0), (k - 1, 0), (0, 0)))
    z = sum(taps[j] * padded[:, j:j + t] for j in range(k))
    return jnp.dot(gate_out * z, conv["out_proj"]["kernel"])


def attention(a, attn, kind: str, model: Mapping[str, Any]):
    b, t, _ = a.shape
    g, d, eps = model["num_kv_heads"], model["head_dim"], model["rms_eps"]
    n = model["num_heads"] // g
    q = jnp.dot(a, attn["q"]["kernel"]).reshape(b, t, g * n, d)
    k = jnp.dot(a, attn["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, attn["v"]["kernel"]).reshape(b, t, g, d)
    if model["qk_norm"]:
        q, k = _rms_norm(q, attn["q_norm"], eps), \
            _rms_norm(k, attn["k_norm"], eps)
    window = None
    if kind in ("window_rope", "full_rope"):
        if kind == "window_rope":
            window = model["window"]
        q, k = _rotary(q, model["rope_theta"]), _rotary(k, model["rope_theta"])
    elif kind != "full_nope":
        raise ValueError(f"unknown layer kind {kind!r}")
    ctx = _attention(q.reshape(b, t, g, n, d), k, v, window)
    return jnp.dot(ctx, attn["out"]["kernel"])


def _layer(p, x, layer: int, kind: str, model: Mapping[str, Any],
           chosen=None):
    a = _rms_norm(x, p["attn_norm"], model["rms_eps"])
    if kind == "short_conv":
        h = x + short_conv(a, p["conv"])
    else:
        h = x + attention(a, p["attn"], kind, model)
    m = _rms_norm(h, p["ff_norm"], model["rms_eps"])
    if layer < model["num_dense_layers"]:
        f = gated_block(m, p["ff"]["dense"])
    else:
        idx, weights = route(m, p["ff"], model, chosen)
        f = expert_sum(m, idx, weights, p["ff"]["experts"],
                       model["expert_offset"])
    return h + f, m


def _embed(p, text, image, model: Mapping[str, Any]):
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    return ids, p["token_emb"][ids]


def chosen_experts(params, text, image, model: Mapping[str, Any]):
    """(expert layers, B, T, k): the experts every token chooses in every
    expert layer, in float32 (what ``probes/trinity_precision.py`` sets
    the program's bfloat16 choices against: near-ties flip)."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        chosen = []
        with jax.default_matmul_precision("highest"):
            _, x = _embed(p, text, image, model)
            for i, kind in enumerate(layer_kinds(model)):
                lp = p[f"layer_{i}"]
                x, m = _layer(lp, x, i, kind, model)
                if i >= model["num_dense_layers"]:
                    chosen.append(route(m, lp["ff"], model)[0])
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
    dense = model["num_dense_layers"]
    for i, kind in enumerate(layer_kinds(model)):
        layer = lambda lp, x, sets, i=i, kind=kind: _layer(
            lp, x, i, kind, model, sets)[0]
        sets = None if chosen is None or i < dense else chosen[i - dense]
        x = (jax.checkpoint(layer) if checkpoint_blocks else layer)(
            p[f"layer_{i}"], x, sets)
    x = _rms_norm(x, p["final_norm"], model["rms_eps"])

    head = p["token_emb"].T if model["tied_embeddings"] else p["lm_head"]
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


ATTENTION_KINDS = ("full_nope", "window_rope", "full_rope")


def conv_layers(model: Mapping[str, Any]) -> int:
    return layer_kinds(model).count("short_conv")


def attention_pairs(model: Mapping[str, Any], kind: str) -> int:
    """Allowed (query, key) pairs of one head of one sequence: in a
    window layer only the pairs inside the window; none in a layer whose
    operator is the short convolution."""
    if kind not in ATTENTION_KINDS:
        return 0
    t = tokens_per_sample(model)
    seen = np.arange(t) + 1
    if kind == "window_rope":
        seen = np.minimum(seen, model["window"])
    return int(seen.sum())


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


def conv_matmul_params(model: Mapping[str, Any]) -> int:
    """in_proj (hidden x 3 hidden) and out_proj (hidden x hidden)."""
    return 4 * model["hidden_size"] ** 2


def conv_mix_flops_forward(model: Mapping[str, Any]) -> int:
    """``conv/mix`` of one token: K multiply-adds a lane for the taps and
    one multiply each for the two gates."""
    return (2 * model["conv_kernel"] + 2) * model["hidden_size"]


def operator_matmul_params(model: Mapping[str, Any], kind: str) -> int:
    return (conv_matmul_params(model) if kind == "short_conv"
            else attention_matmul_params(model))


def feed_forward_matmul_params(model: Mapping[str, Any],
                               layer: int) -> float:
    """Weights one token is multiplied by in ``layer``'s feed-forward: the
    dense block, or the router and the held experts it is routed to (in
    expectation; no shared expert)."""
    if layer < model["num_dense_layers"]:
        return 3 * model["hidden_size"] * model["dense_width"]
    return (model["hidden_size"] * model["num_experts"]
            + held_assignments_per_token(model) * 3 * model["hidden_size"]
            * model["expert_width"])


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only — each layer's operator (the convolution's two projections and
    its mix, or attention's four and the pairs inside the band), the dense
    block, the router and the held experts' products for the assignments
    they receive in expectation, the tied head over the predicted
    positions."""
    t = tokens_per_sample(model)
    fwd = 0.0
    for layer, kind in enumerate(layer_kinds(model)):
        fwd += 2.0 * t * (operator_matmul_params(model, kind)
                          + feed_forward_matmul_params(model, layer))
        fwd += attention_flops_forward(model, kind)
        if kind == "short_conv":
            fwd += t * conv_mix_flops_forward(model)
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
    sample's forward and backward pass, the attention layers only. The
    useful work of 64-wide heads: pairs x 64 lanes x 4 (what a kernel that
    runs two heads a 128-lane tile spends beyond it is the kernel's).
    Forward reads q and writes the context (T x H x d each) and reads k, v
    (T x G x d each); backward reads q, context, its cotangent, k, v and
    writes dq, dk, dv, at twice the flops."""
    t, d = tokens_per_sample(model), model["head_dim"]
    wide = t * model["num_heads"] * d * act_bytes
    narrow = t * model["num_kv_heads"] * d * act_bytes
    calls = []
    for kind in layer_kinds(model):
        if kind in ATTENTION_KINDS:
            flops = attention_flops_forward(model, kind)
            calls += [(flops, 2 * wide + 2 * narrow),
                      (2 * flops, 4 * wide + 4 * narrow)]
    return _least(calls, peaks)


def short_conv_min_seconds_per_sample(model: Mapping[str, Any],
                                      peaks: Mapping[str, float],
                                      act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in ``conv/mix`` (the two gates
    and the taps, nothing of the projections) of one sample's forward and
    backward pass, whatever implements it: a convolution layer's forward
    reads B, C, u and writes the gated result (4 arrays of T x hidden),
    its backward reads those three and the result's cotangent and writes
    three cotangents (7 arrays) at twice the flops; the taps and their
    gradient are K x hidden numbers and not counted. A replay of the
    forward under rematerialisation is the program's choice and not
    counted, as in ``train_flops_per_sample``."""
    t = tokens_per_sample(model)
    array = t * model["hidden_size"] * act_bytes
    flops = t * conv_mix_flops_forward(model)
    calls = [(flops, 4 * array), (2 * flops, 7 * array)] * conv_layers(model)
    return _least(calls, peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: three products an
    expert layer over the assignments the held experts receive in
    expectation (the dense block is no grouped product and is not
    counted). Bytes are the rows in and out (the weights are read once for
    all the samples of a step's micro-batch)."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 3 * dim * width * rows
    nbytes = rows * (2 * dim + 3 * width) * act_bytes
    calls = [(flops, nbytes), (2 * flops, 2 * nbytes)] * expert_layers(model)
    return _least(calls, peaks)
