"""The ``smallthinker`` yardstick: what the benchmark knows about the
architecture of SmallThinker-21BA3B-Instruct (PowerInfer; config.json at
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) — the plain
reference that decides ``correct``, and the counts behind ``mfu_pct``,
``attn_roofline.*`` and ``moe_experts_roofline``. Contract: the docstring
of ``yardsticks/dalle.py``.

**One layer** (x: (T, hidden), f32 throughout, ``highest`` matmuls):

    a     = rmsnorm(x; g1, eps)
    r     = a . W_r                       the router reads the normed layer input
    q,k,v = a.W_q (H x d), a.W_k (G x d), a.W_v (G x d)         no biases
    window_rope layers: q, k <- rotary(q, k; theta, rotate-half over all d,
                        position = index); full_nope layers: nothing added
    s_ij  = q_i.k_j / sqrt(d), query head h reads key-value head h // (H/G);
            j <= i, and in a window_rope layer also i - j < window
    h     = x + concat_h(softmax(s) v) . W_o
    m     = rmsnorm(h; g2, eps)
    S     = the k largest of r;  p = softmax(r_S)
    y     = sum_{e in S, e held} p_e . W_down,e(relu(W_gate,e m) * (W_up,e m))
    out   = h + y

then a final RMSNorm, an untied head and the mean next-token cross-entropy
over the T - 1 predicted positions of ``[text || image + vocab_text]``.

**Departures from the published description, each as the configuration
file states it:**

- ``experts_held`` of the ``num_experts`` experts are held (from
  ``expert_offset``): the router scores all of them and ``y`` sums over the
  held ones only. What the absent experts would add is left out here as in
  the program (guide ``model-configs`` section 4); ``whole_layer_experts``
  gives the uncut sum for the test that adds the shares up.
- ``vocab_size`` is a slice of the published vocabulary: embedding, head
  and loss are over the slice.
- ``num_hidden_layers`` is one period of ``layer_kinds`` (published: 13).
- ``router_input = "input_norm"`` and ``attention_bias = false`` are
  assumed (the catalog pins neither); ``described_as`` mentions secondary
  experts, which have no key in the config: none.
- the sequence reaches the model as the trainer's two fields, ``text`` and
  ``image`` (ids offset by ``vocab_text``), concatenated.
- ``embed_init_std`` (assumed; no source pins an embedding's scale) is the
  program's initialiser, not part of these equations: the reference takes
  the parameters it is given. At 1.0 an untrained router reads tokens and
  spreads them evenly; the load the cell times is that, not a trained
  router's.

**Near-ties.** The k largest of r is not continuous: where the k-th and
the next score are closer than the program's bfloat16 activations resolve,
the program and this reference choose different sets (about 2% of the
tokens a layer at the cell's sizes), and a token's expert and routing
gradients then land on other experts. Against ``loss_and_grads`` as the
harness calls it that is most of the worst leaf's distance.
``loss_and_grads_at`` evaluates the reference at given sets (the ones the
program took, which it sows), p = softmax of its own scores there, and
what is left is rounding (``probes/smallthinker_precision.py`` reads both).

Four things keep the float32 reference inside one chip's memory at 8 192
tokens; none changes the arithmetic: query rows go through attention in
blocks (28 x 8192 x 8192 f32 scores never exist at once), the head's rows
in chunks, the experts one at a time, each under ``jax.checkpoint``, and
with ``checkpoint_blocks`` every layer is too. The expert sum is dense: every held expert on every
token, times its routing weight (0 where the token was not routed to it).
Both check sequences go through one call: the gradients are those of the
mean, with no running sum.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

MASK_FILL = -1e30
QUERY_BLOCK = 256
HEAD_CHUNK = 2048


def layer_kinds(model: Mapping[str, Any]):
    kinds = model["layer_kinds"]
    return [kinds[i % len(kinds)] for i in range(model["num_hidden_layers"])]


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


def route(a, router, k: int, chosen=None):
    """The k largest router scores of every token and the softmax over
    them: (ids, weights), each (..., k). With ``chosen`` (..., k) those
    ids stand for the k largest."""
    scores = jnp.dot(a, router)
    if chosen is None:
        top, chosen = jax.lax.top_k(scores, k)
    else:
        top = jnp.take_along_axis(scores, chosen, -1)
    return chosen, jax.nn.softmax(top, -1)


def expert_sum(m, idx, p, experts, first: int):
    """sum over the experts of ``experts`` (leaves stacked on the leading
    axis; the first is expert ``first`` of the router's) of routing weight
    x expert(m); a token not routed to an expert weighs 0 there. Each
    expert's products are computed again in the backward pass: the eight
    experts' (tokens x width) intermediates never stand side by side."""
    @jax.checkpoint
    def one(y, xs):
        e, gate, up, down = xs
        weight = jnp.sum(jnp.where(idx == e, p, 0.0), -1)
        out = jnp.dot(jax.nn.relu(jnp.dot(m, gate)) * jnp.dot(m, up), down)
        return y + weight[..., None] * out, None

    n = experts["gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(m),
                        (first + jnp.arange(n), experts["gate"],
                         experts["up"], experts["down"]))
    return y


def whole_layer_experts(m, a, router, experts, k: int):
    """The uncut expert layer: ``experts`` holds all of the router's."""
    idx, p = route(a, router, k)
    return expert_sum(m, idx, p, experts, 0)


def _layer(p, x, kind: str, model: Mapping[str, Any], chosen=None):
    b, t, _ = x.shape
    g, d = model["num_kv_heads"], model["head_dim"]
    n = model["num_heads"] // g
    a = _rms_norm(x, p["attn_norm"], model["rms_eps"])
    idx, weights = route(a, p["ff"]["router"], model["experts_per_token"],
                         chosen)
    q = jnp.dot(a, p["attn"]["q"]["kernel"]).reshape(b, t, g * n, d)
    k = jnp.dot(a, p["attn"]["k"]["kernel"]).reshape(b, t, g, d)
    v = jnp.dot(a, p["attn"]["v"]["kernel"]).reshape(b, t, g, d)
    window = None
    if kind == "window_rope":
        window = model["window"]
        q, k = _rotary(q, model["rope_theta"]), _rotary(k, model["rope_theta"])
    elif kind != "full_nope":
        raise ValueError(f"unknown layer kind {kind!r}")
    ctx = _attention(q.reshape(b, t, g, n, d), k, v, window)
    h = x + jnp.dot(ctx, p["attn"]["out"]["kernel"])
    m = _rms_norm(h, p["ff_norm"], model["rms_eps"])
    return h + expert_sum(m, idx, weights, p["ff"]["experts"],
                          model["expert_offset"])


def chosen_experts(params, text, image, model: Mapping[str, Any]):
    """(layers, B, T, k): the experts every token chooses in every layer,
    in float32 (what ``probes/smallthinker_precision.py`` sets the
    program's bfloat16 choices against: near-ties flip)."""
    def run(params, text, image):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)["params"]
        chosen = []
        with jax.default_matmul_precision("highest"):
            x = p["token_emb"][
                jnp.concatenate([text, image + model["vocab_text"]], 1)]
            for i, kind in enumerate(layer_kinds(model)):
                lp = p[f"layer_{i}"]
                a = _rms_norm(x, lp["attn_norm"], model["rms_eps"])
                chosen.append(route(a, lp["ff"]["router"],
                                    model["experts_per_token"])[0])
                x = _layer(lp, x, kind, model)
        return jnp.stack(chosen)
    return jax.jit(run)(params, text, image)


def loss_fn(params, text, image, model: Mapping[str, Any],
            checkpoint_blocks: bool = False, chosen=None):
    """Mean next-token cross-entropy over the T - 1 predicted positions;
    returns ``(loss, (loss_text, loss_img))``, the means over the targets
    of the two fields. ``chosen``: (layers, B, T, k) expert ids to route
    by (module docstring, near-ties); None: the reference's own."""
    p = params["params"]
    ids = jnp.concatenate([text, image + model["vocab_text"]], 1)
    x = p["token_emb"][ids]
    for i, kind in enumerate(layer_kinds(model)):
        layer = lambda lp, x, sets, kind=kind: _layer(lp, x, kind, model,
                                                      sets)
        x = (jax.checkpoint(layer) if checkpoint_blocks else layer)(
            p[f"layer_{i}"], x, None if chosen is None else chosen[i])
    x = _rms_norm(x, p["final_norm"], model["rms_eps"])

    b, t = ids.shape
    rows = _block_size(b * (t - 1), HEAD_CHUNK)

    @jax.checkpoint
    def chunk(args):
        h, target = args
        logp = jax.nn.log_softmax(jnp.dot(h, p["lm_head"]), -1)
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
    k) instead of the reference's own (module docstring, near-ties)."""
    return _loss_and_grads(params, text, image, model, checkpoint_blocks,
                           jnp.asarray(chosen))


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def attention_pairs(model: Mapping[str, Any], kind: str) -> int:
    """Allowed (query, key) pairs of one head of one sequence."""
    t = tokens_per_sample(model)
    i = np.arange(t)
    seen = i + 1
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


def layer_matmul_params(model: Mapping[str, Any]) -> float:
    """Weights one token is multiplied by in one layer: q, k, v, out, the
    router, and the held experts it is routed to (in expectation)."""
    dim, d = model["hidden_size"], model["head_dim"]
    attn = 2 * dim * d * (model["num_heads"] + model["num_kv_heads"])
    expert = 3 * dim * model["expert_width"]
    return (attn + dim * model["num_experts"]
            + held_assignments_per_token(model) * expert)


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample: required work
    only — the projections and the router, attention over the pairs inside
    the band, the held experts' products for the assignments they receive
    in expectation, the sliced head over the predicted positions."""
    t = tokens_per_sample(model)
    fwd = 2.0 * model["num_hidden_layers"] * layer_matmul_params(model) * t
    fwd += sum(attention_flops_forward(model, kind)
               for kind in layer_kinds(model))
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
    sample's forward and backward pass. Forward reads q and writes the
    context (T x H x d each) and reads k, v (T x G x d each); backward
    reads q, context, its cotangent, k, v and writes dq, dk, dv, at twice
    the flops."""
    t, d = tokens_per_sample(model), model["head_dim"]
    wide = t * model["num_heads"] * d * act_bytes
    narrow = t * model["num_kv_heads"] * d * act_bytes
    calls = []
    for kind in layer_kinds(model):
        flops = attention_flops_forward(model, kind)
        calls += [(flops, 2 * wide + 2 * narrow),
                  (2 * flops, 4 * wide + 4 * narrow)]
    return _least(calls, peaks)


def experts_min_seconds_per_sample(model: Mapping[str, Any],
                                   peaks: Mapping[str, float],
                                   act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the held experts' grouped
    products of one sample's forward and backward pass: three products a
    layer over the assignments the held experts receive in expectation.
    Bytes are the rows in and out (the weights are read once for all the
    samples of a step's micro-batch, and are not counted a sample)."""
    rows = tokens_per_sample(model) * held_assignments_per_token(model)
    dim, width = model["hidden_size"], model["expert_width"]
    flops = 2.0 * 3 * dim * width * rows
    nbytes = rows * (2 * dim + 3 * width) * act_bytes
    calls = [(flops, nbytes), (2 * flops, 2 * nbytes)] \
        * model["num_hidden_layers"]
    return _least(calls, peaks)
