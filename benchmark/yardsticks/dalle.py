"""The ``dalle`` yardstick: what the benchmark knows about the DALL-E
architecture of learning-at-home/dalle (dalle-pytorch's DALLE as ``task.py:62-83``
configures it) — the plain reference that decides ``correct``, and the
counts that turn a rate into ``mfu_pct`` and a kernel's device time into
its roofline share.

**The contract of a yardstick** (every ``benchmark/yardsticks/<name>.py``;
``tests/benchmark_tests/test_benchmark_yardstick.py`` holds each to it). A
configuration file names its yardstick (``"yardstick": "<name>"``, absent:
``dalle``); ``Manifest.yardstick`` loads the file by its path under the
manifest's root, and harness and reducers reach it only as
``cell.yardstick`` / ``ctx.yardstick``. ``model`` is the ``model`` group
of the configuration file. The module holds:

- ``loss_and_grads(params, text, image, model, checkpoint_blocks=False)
  -> (loss, grads)``: the architecture's loss and every parameter's
  gradient, written from its equations in ``jax.numpy``, float32 under
  ``jax.default_matmul_precision("highest")``: no flax module, no kernel,
  no cache, nothing imported from the program. ``grads`` has the
  structure of ``params`` (the harness zips their leaves);
- ``tokens_per_sample(model)`` and ``train_flops_per_sample(model)``: the
  operations the forward and backward passes *require* of one sample —
  matmuls at 2 flops a multiply-add, attention over the allowed (query,
  key) pairs only, backward at twice the forward. Replays under
  rematerialisation, an overhanging scan block and masked-out score tiles
  are work the program chose, not work the model needs, and are not
  counted. ``train_tokens_per_s`` and ``mfu_pct`` read them;
- any number of *least seconds* functions ``f(model, peaks) ->
  {"seconds": ...}``: the least time one chip can spend in one kernel
  family over one sample's forward and backward pass, per call the larger
  of flops / peak and bytes / bandwidth. A ``layer_metrics/<m>.json`` of
  the ``kernel_roofline`` reducer names one under ``least``. Here:
  ``attention_min_seconds_per_sample``.

The chip's peaks (``peaks.json``, ``harness.peaks_for``) belong to no
architecture and stay common.

**The reference.** It takes only the numbers of a configuration file and
the parameter tree (the names flax gives the system's parameters are the
one thing shared with the program): dense masked attention, pre-norm
LayerNorm, rotate-half rotary, GEGLU, the tied table as the head.

Two departures from "plain", noted where they are made (``_run_layers``):
the layers run as a ``lax.scan`` that picks each layer's parameters and
mask by index (unrolled, the program is a 540 MB executable), and with
``checkpoint_blocks`` the iteration is wrapped in ``jax.checkpoint`` (a
float32 backward pass through 64 layers saves ~20 GB of activations a
sequence without it, and the chip has 16). Neither changes the arithmetic.

The sequence is scored unshifted: position p receives the embedding of the
token before it (BOS at p = 0) and predicts token p of
``[text || image + vocab_text]``. Text positions are scored against the
text rows of the tied table only, image positions against the image rows.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6
ROTARY_BASE = 10000.0
MASK_FILL = -1e9


def layer_schedule(model: Mapping[str, Any]) -> List[Tuple[int, str]]:
    """(unique block id, attention type) of every layer; id -1 is the final
    conv-like block with parameters of its own."""
    body = model["depth"] - (1 if model["final_conv_block"] else 0)
    cycle = model["shared_block_cycle"] or body
    types = model["attn_types"]
    sched = [(i % cycle, types[(i % cycle) % len(types)])
             for i in range(body)]
    if model["final_conv_block"]:
        sched.append((-1, "conv_like"))
    return sched


def attention_mask(attn_type: str, text_len: int, grid: int,
                   conv_kernel: int) -> np.ndarray:
    """(T, T) bool, True where query p may attend key s. Text queries see
    the text before them; image queries see all text plus, of the image
    tokens up to themselves: all (full), their row (axial_row), their
    column (axial_col), or a conv_kernel x conv_kernel neighbourhood
    (conv_like)."""
    n_img = grid * grid
    total = text_len + n_img
    mask = np.zeros((total, total), bool)
    t = np.arange(text_len)
    mask[:text_len, :text_len] = t[None, :] <= t[:, None]
    mask[text_len:, :text_len] = True
    i = np.arange(n_img)
    qr, qc, kr, kc = i[:, None] // grid, i[:, None] % grid, \
        i[None, :] // grid, i[None, :] % grid
    earlier = i[None, :] <= i[:, None]
    if attn_type == "full":
        img = earlier
    elif attn_type == "axial_row":
        img = (kr == qr) & (kc <= qc)
    elif attn_type == "axial_col":
        img = (kc == qc) & (kr <= qr)
    elif attn_type == "conv_like":
        half = conv_kernel // 2
        img = (abs(kr - qr) <= half) & (abs(kc - qc) <= half) & earlier
    else:
        raise ValueError(f"unknown attention type {attn_type!r}")
    mask[text_len:, text_len:] = img
    return mask


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rotary(x, head_dim: int):
    """x: (B, T, H, d). Rotate-half rotary over absolute positions."""
    half = head_dim // 2
    freqs = 1.0 / (ROTARY_BASE ** (jnp.arange(half, dtype=jnp.float32)
                                   / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, mask, heads: int, head_dim: int, rotary: bool):
    """Pre-norm attention and GEGLU feed-forward, each with a residual."""
    b, t, _ = x.shape
    h = _layer_norm(x, p["attn_norm"])
    q, k, v = (jnp.dot(h, p["attn"][n]["kernel"]).reshape(
        b, t, heads, head_dim) for n in ("q", "k", "v"))
    if rotary:
        q, k = _rotary(q, head_dim), _rotary(k, head_dim)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * head_dim ** -0.5
    w = jax.nn.softmax(jnp.where(mask[None, None], s, MASK_FILL), -1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, t, -1)
    x = x + jnp.dot(ctx, p["attn"]["out"]["kernel"]) + p["attn"]["out"]["bias"]
    h = _layer_norm(x, p["ff_norm"])
    ff = p["ff"]
    val = jnp.dot(h, ff["wi"]["kernel"]) + ff["wi"]["bias"]
    gate = jnp.dot(h, ff["gate"]["kernel"]) + ff["gate"]["bias"]
    return x + jnp.dot(val * _gelu_tanh(gate), ff["wo"]["kernel"]) \
        + ff["wo"]["bias"]


def _block_params(tr: Mapping[str, Any], uid: int):
    if uid == -1:
        return tr["block_wconv"]
    shared = tr.get("cycle", tr)   # weight-shared blocks sit under "cycle"
    return shared[f"block_{uid}"]


def _run_layers(tr, x, masks, model: Mapping[str, Any], checkpoint: bool):
    """Apply the layer schedule as one ``lax.scan`` over the layers: every
    iteration picks its block's parameters and its mask out of a stack by
    index and applies ``_block``. Unrolled, the 64 float32 layers at the
    highest matmul precision are a 540 MB TPU executable (about 40 MB of
    code a block) that takes 220 s to compile and that no cache keeps; as
    a loop the program holds one block. That, and ``jax.checkpoint``
    around the iteration (memory only), are the two departures from
    "plain"; neither changes the arithmetic of a layer."""
    sched = layer_schedule(model)
    uids = sorted({uid for uid, _ in sched})
    kinds = sorted(masks)
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *[_block_params(tr, uid) for uid in uids])
    mask_stack = jnp.stack([masks[k] for k in kinds])
    which = jnp.asarray([[uids.index(uid), kinds.index(kind)]
                         for uid, kind in sched], jnp.int32)

    def layer(x, idx):
        p = jax.tree.map(lambda a: a[idx[0]], stacked)
        return _block(p, x, mask_stack[idx[1]], model["heads"],
                      model["head_dim"], model["rotary"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer) if checkpoint else layer,
                        x, which)
    return x


def masks_for(model: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """The (T, T) attention mask of every kind of layer in the schedule."""
    return {kind: attention_mask(kind, model["text_seq_len"],
                                 model["image_grid"], model["conv_kernel"])
            for kind in sorted({k for _, k in layer_schedule(model)})}


def loss_fn(params, text, image, masks, model: Mapping[str, Any],
            checkpoint_blocks: bool = False):
    """Weighted next-token cross-entropy of ``[text || image]``:
    ``(loss_text + w * loss_img) / (1 + w)``, each a mean over its
    positions. Returns ``(loss, (loss_text, loss_img))``."""
    p = params["params"]
    vt, vi = model["vocab_text"], model["vocab_image"]
    tl, grid = model["text_seq_len"], model["image_grid"]
    labels = jnp.concatenate([text, image + vt], 1)
    bos = jnp.full((labels.shape[0], 1), vt + vi, labels.dtype)
    inputs = jnp.concatenate([bos, labels[:, :-1]], 1)
    table = p["token_emb"]
    img_pos = (p["img_row_emb"][:, None] + p["img_col_emb"][None]).reshape(
        grid * grid, -1)
    x = table[inputs] + jnp.concatenate([p["text_pos_emb"], img_pos], 0)[None]

    x = _run_layers(p["transformer"], x, masks, model, checkpoint_blocks)
    x = _layer_norm(x, p["transformer"]["final_norm"])

    def nll(h, rows, targets):
        logp = jax.nn.log_softmax(jnp.dot(h, rows.T), -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    loss_text = nll(x[:, :tl], table[:vt], text).mean()
    loss_img = nll(x[:, tl:], table[vt:vt + vi], image).mean()
    w = model["loss_img_weight"]
    return (loss_text + w * loss_img) / (1.0 + w), (loss_text, loss_img)


def make_loss_and_grads(model: Mapping[str, Any],
                        checkpoint_blocks: bool = False):
    """Jitted ``(params, text, image, masks) -> (loss, grads)`` of the
    reference: float32 at the highest matmul precision, every parameter's
    gradient. The masks (``masks_for``) are operands, not constants: folded
    into the program they made a 488 MB executable that no cache kept."""
    def run(params, text, image, masks):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            (loss, _), grads = jax.value_and_grad(
                lambda q: loss_fn(q, text, image, masks, model,
                                  checkpoint_blocks),
                has_aux=True)(params)
        return loss, grads
    return jax.jit(run)


def loss_and_grads(params, text, image, model: Mapping[str, Any],
                   checkpoint_blocks: bool = False):
    """Loss and gradients of the mean over the sequences of ``text`` /
    ``image``. One sequence at a time through the same jitted program, so
    that the float32 activations of one sequence are all the chip has to
    hold; every sequence has the same number of positions, so the mean of
    the per-sequence losses is the batch loss."""
    run = make_loss_and_grads(model, checkpoint_blocks)
    masks = {k: jnp.asarray(m) for k, m in masks_for(model).items()}
    n = text.shape[0]
    loss, grads = run(params, text[:1], image[:1], masks)
    for i in range(1, n):
        loss_i, grads_i = run(params, text[i:i + 1], image[i:i + 1], masks)
        loss = loss + loss_i
        grads = jax.tree.map(jnp.add, grads, grads_i)
    return loss / n, jax.tree.map(lambda g: g / n, grads)


# -- the counts: operations and bytes from shapes alone ----------------------

def tokens_per_sample(model: Mapping[str, Any]) -> int:
    return model["text_seq_len"] + model["image_grid"] ** 2


def attention_pairs(model: Mapping[str, Any], attn_type: str) -> int:
    """Allowed (query, key) pairs of one head of one sequence."""
    return int(attention_mask(attn_type, model["text_seq_len"],
                              model["image_grid"],
                              model["conv_kernel"]).sum())


def block_matmul_params(model: Mapping[str, Any]) -> int:
    """Weights one token is multiplied by in one block: q, k, v, out
    projections and the GEGLU feed-forward's value, gate and output."""
    d, inner = model["dim"], model["ff_mult"] * model["dim"]
    return 4 * d * d + 3 * d * inner


def head_params_per_token(model: Mapping[str, Any]) -> float:
    """Rows of the tied table a position is scored against, times dim,
    averaged over the sequence: text positions see the text rows only and
    image positions the image rows."""
    tl, il = model["text_seq_len"], model["image_grid"] ** 2
    rows = (tl * model["vocab_text"] + il * model["vocab_image"]) / (tl + il)
    return rows * model["dim"]


def effective_params(model: Mapping[str, Any]) -> float:
    """Weights a token meets on its way through the model (shared blocks
    count once per layer that applies them)."""
    return (model["depth"] * block_matmul_params(model)
            + head_params_per_token(model))


def attention_flops_forward(model: Mapping[str, Any], attn_type: str) -> int:
    """QK^T and PV of one sequence, all heads, allowed pairs only."""
    return (4 * attention_pairs(model, attn_type) * model["head_dim"]
            * model["heads"])


def train_flops_per_sample(model: Mapping[str, Any]) -> float:
    """Forward plus backward (2x forward) of one sample."""
    fwd = 2 * effective_params(model) * tokens_per_sample(model)
    fwd += sum(attention_flops_forward(model, kind)
               for _, kind in layer_schedule(model))
    return 3.0 * fwd


def attention_min_seconds_per_sample(model: Mapping[str, Any],
                                     peaks: Mapping[str, float],
                                     act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip can spend in the attention of one sample's
    forward and backward pass: per layer and direction the larger of
    flops / peak and bytes / bandwidth. Forward reads q, k, v and writes
    the context (4 tensors of T x dim); backward reads q, k, v, context and
    its cotangent and writes dq, dk, dv (8 tensors) at twice the flops.
    Returns the seconds and how much of them is bound by bandwidth."""
    tensor = tokens_per_sample(model) * model["dim"] * act_bytes
    total = by_bytes = 0.0
    for _, kind in layer_schedule(model):
        flops = attention_flops_forward(model, kind)
        for n_tensors, mult in ((4, 1.0), (8, 2.0)):
            t_flops = mult * flops / peaks["bf16_flops_per_s"]
            t_bytes = n_tensors * tensor / peaks["hbm_bytes_per_s"]
            total += max(t_flops, t_bytes)
            by_bytes += t_bytes if t_bytes >= t_flops else 0.0
    return {"seconds": total, "bandwidth_bound_share": by_bytes / total}
