"""KV-cached autoregressive image generation.

Capability parity with dalle-pytorch's ``generate_images`` as the reference
drives it (``inference/run_inference.py:87-90`` of learning-at-home/dalle:
``use_cache=True``, temperature / top-k / top-p sampling of 1024 VQGAN
codes). TPU-native shape: the whole decode is ONE ``lax.scan`` over the
1280 positions (256 teacher-forced text + 1024 sampled image codes) with a
static-shape KV cache per layer application — no Python loop, no dynamic
shapes, compiled once.

The incremental math here is a hand-rolled mirror of the Flax modules in
``transformer.py`` (LayerNorm -> q/k/v -> rotary -> masked single-query
attention against the cache -> out -> GEGLU FF), reading the same parameter
tree the trainer produces (both the ``nn.scan`` ``cycle/block_i`` layout
and the unrolled ``block_i`` layout). Exactness is enforced by test:
teacher-forced cached decode must reproduce the training forward's logits.

Per-layer masking reuses :func:`zoo_attention_mask` rows, so every zoo
type (axial_row/col, conv_like, full) decodes with exactly its training
sparsity.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dalle_tpu.config import (LAYER_GATED_DELTA, LAYER_MAMBA2,
                              LAYER_SELECTED_ROPE, ModelConfig)
from dalle_tpu.models.attention import (NEG_INF, apply_rotary_lanes,
                                        rotary_cos_sin, zoo_attention_mask)

LN_EPS = 1e-6  # flax nn.LayerNorm default


class SamplingConfig(NamedTuple):
    """Reference CLI flags (inference/run_inference.py:96-105)."""

    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0      # 1.0 = disabled


def layer_params(params: Dict, cfg: ModelConfig) -> List[Dict]:
    """Per-layer-application parameter dicts following layer_schedule().

    Accepts both the trainer's ``nn.scan`` tree (``transformer/cycle/
    block_i``) and the unrolled tree (``transformer/block_i``).
    """
    root = params["params"] if "params" in params else params
    tr = root["transformer"]
    blocks = dict(tr.get("cycle", {}))
    for key, val in tr.items():
        if key.startswith("block"):
            blocks[key] = val
    group = len(cfg.attn_types)
    # the stacked tree exists only when the dense stack actually scanned
    # (cfg.dense_scan_reps() is the one source of truth, shared with the
    # transformer build); shallow dense_scan configs unroll and store
    # plain block_{uid} params
    dense_stacked = cfg.dense_scan_reps() > 0
    out = []
    for uid, attn_type in cfg.layer_schedule():
        if dense_stacked and uid != -1:
            # dense_scan tree: cycle/block_{uid%group} with a leading
            # stacked axis of scan repetitions — slice this layer's rep
            rep, sub = divmod(uid, group)
            sliced = jax.tree.map(lambda a: a[rep],
                                  blocks[f"block_{sub}"])
            out.append({"attn_type": attn_type, **sliced})
            continue
        name = "block_wconv" if uid == -1 else f"block_{uid}"
        out.append({"attn_type": attn_type, **blocks[name]})
    return out


def _ln(x, p, dtype):
    """LayerNorm mirroring flax nn.LayerNorm(dtype=...): stats in f32, the
    result cast back to the activation dtype so fp32 scale/bias params do
    not silently promote the whole decode to f32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + LN_EPS)
    return (y * p["scale"] + p.get("bias", 0.0)).astype(dtype)


def _cycle_reps(cfg: ModelConfig) -> int:
    """Number of scan repetitions over the weight-shared cycle (0 when the
    schedule is not cycle-structured and decode unrolls instead)."""
    body = len(cfg.layer_schedule()) - (1 if cfg.final_conv_block else 0)
    cycle = cfg.shared_block_cycle
    if cycle and -(-body // cycle) > 1:
        return -(-body // cycle)
    return 0


def n_cache_slots(cfg: ModelConfig) -> int:
    """KV-cache slots. The scanned decode sizes the body as reps x cycle
    (the final repetition's overhanging applications own dead slots, same
    as training's masked scan overhang); the unrolled decode uses exactly
    one slot per schedule entry."""
    reps = _cycle_reps(cfg)
    if reps:
        return (reps * cfg.shared_block_cycle
                + (1 if cfg.final_conv_block else 0))
    return len(cfg.layer_schedule())


def refuse_selected_layers(cfg) -> None:
    """A configuration with a layer of kind ``selected_rope`` (attention
    over the keys an indexer chose, models/sparse_lm.py) cannot be decoded
    here, and is told so by the kind's name in one sentence."""
    kind = LAYER_SELECTED_ROPE
    if kind in getattr(cfg, "layer_kinds", ()):
        raise NotImplementedError(
            f"models/decode.py cannot decode a layer of kind {kind!r}: it "
            "keeps no cache of the indexer's one key head and has no way to "
            "choose a query's keys at decode time")


def refuse_recurrent_layers(cfg) -> None:
    """A configuration with a layer of kind ``mamba2`` (a state-space
    mixer, models/sparse_lm.py) or ``gated_delta`` (a gated-delta-rule
    mixer) cannot be decoded here either: the cache below holds keys and
    values, not a recurrence's state."""
    kinds = getattr(cfg, "layer_kinds", ())
    if LAYER_MAMBA2 in kinds:
        raise NotImplementedError(
            f"models/decode.py cannot decode a layer of kind "
            f"{LAYER_MAMBA2!r}: it "
            "keeps no cache of the recurrence's state a head and of the "
            "convolution's last taps' tokens, and has no single-token step "
            "of the scan")
    if LAYER_GATED_DELTA in kinds:
        raise NotImplementedError(
            f"models/decode.py cannot decode a layer of kind "
            f"{LAYER_GATED_DELTA!r}: it keeps no cache of the (key x value) "
            "state a head and of the convolution's last taps' tokens, and "
            "has no single-token step of the delta rule")


def refuse_looped_stack(cfg) -> None:
    """A configuration whose stack of layers is run ``total_ut_steps``
    times on one set of parameters (models/sparse_lm.py) cannot be decoded
    here: the cache below is one a layer."""
    passes = getattr(cfg, "total_ut_steps", 1)
    if passes > 1:
        raise NotImplementedError(
            f"models/decode.py cannot decode a stack of layers that is run "
            f"{passes} times on one set of parameters: its cache is one a "
            "layer where such a stack needs one a pass and layer, and it "
            "has no exit gate to stop a token's passes by")


def init_cache(cfg: ModelConfig, batch: int, dtype=None):
    """Static-shape KV cache, one k/v pair per layer application (weight
    sharing shares parameters, not activations).

    Layout: heads and head_dim are MERGED into the minor axis (B, T, H*d)
    — with d=64 a (..., H, 64) layout pads every (8, 128) TPU tile 2x,
    which at the flagship's 16-image decode doubles a 5 GB cache
    (measured: the unmerged layout put decode 15 GB past HBM). The
    cycle-structured decode also splits the scanned body from the w_conv
    slot so the scan carries its cache without slicing a big array.
    """
    refuse_selected_layers(cfg)
    refuse_recurrent_layers(cfg)
    refuse_looped_stack(cfg)
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.heads * cfg.head_dim
    reps = _cycle_reps(cfg)
    if reps:
        cycle = cfg.shared_block_cycle
        out = {
            "k_body": jnp.zeros((reps, cycle, batch, cfg.total_seq_len, hd),
                                dtype),
            "v_body": jnp.zeros((reps, cycle, batch, cfg.total_seq_len, hd),
                                dtype),
        }
        if cfg.final_conv_block:
            out["k_conv"] = jnp.zeros((batch, cfg.total_seq_len, hd), dtype)
            out["v_conv"] = jnp.zeros((batch, cfg.total_seq_len, hd), dtype)
        return out
    shape = (n_cache_slots(cfg), batch, cfg.total_seq_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@functools.lru_cache(maxsize=8)
def _mask_stack(cfg: ModelConfig) -> np.ndarray:
    """(n_layers, T, T) per-layer-application decode masks."""
    return np.stack([
        zoo_attention_mask(attn_type, cfg.text_seq_len, cfg.image_grid,
                           cfg.conv_kernel)
        for _, attn_type in cfg.layer_schedule()])


def _positional_table(params: Dict, cfg: ModelConfig) -> jax.Array:
    root = params["params"] if "params" in params else params
    img_pos = (root["img_row_emb"][:, None, :]
               + root["img_col_emb"][None, :, :]).reshape(
                   cfg.image_seq_len, cfg.dim)
    return jnp.concatenate([root["text_pos_emb"], img_pos], axis=0)


def _qkv_rows(x, lp, cos_p, sin_p, cfg: ModelConfig, dtype):
    """The block's q/k/v rows for the current position: (B, H, d) each.

    ``cos_p``/``sin_p`` are (H*d,) when every row shares one position, or
    (B, H*d) when each batch row sits at its own position (the serving
    engine's per-slot decode)."""
    b = x.shape[0]
    h = _ln(x, lp["attn_norm"], dtype)
    q, k, v = (h @ lp["attn"][name]["kernel"].astype(dtype)
               for name in ("q", "k", "v"))
    if cfg.rotary:
        q = apply_rotary_lanes(q, cos_p, sin_p, cfg.head_dim)
        k = apply_rotary_lanes(k, cos_p, sin_p, cfg.head_dim)
    q, k, v = (a.reshape(b, cfg.heads, cfg.head_dim) for a in (q, k, v))
    return q, k, v


def _attend_and_ff(x, lp, q, k_cache, v_cache, mask_row,
                   cfg: ModelConfig, dtype):
    """Attention of the current row over the block's (B, T, H*d) cache,
    out-projection, and the GEGLU FF: (B, dim) -> (B, dim).

    ``mask_row`` is (T,) when the batch shares one position, or (B, T)
    when every row carries its own mask row (per-slot decode)."""
    b, t_total = k_cache.shape[0], k_cache.shape[1]
    scale = cfg.head_dim ** -0.5
    k_view = k_cache.reshape(b, t_total, cfg.heads, cfg.head_dim)
    v_view = v_cache.reshape(b, t_total, cfg.heads, cfg.head_dim)
    scores = jnp.einsum("bhd,bthd->bht", q, k_view.astype(dtype),
                        preferred_element_type=jnp.float32) * scale
    mask_b = (mask_row[None, None, :] if mask_row.ndim == 1
              else mask_row[:, None, :])
    scores = jnp.where(mask_b, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,bthd->bhd", probs.astype(dtype),
                     v_view.astype(dtype),
                     preferred_element_type=jnp.float32).astype(dtype)
    # q/k/v are bias-free (ZooAttention use_bias=False) but the OUT
    # projection keeps nn.Dense's default bias — dropping it desyncs
    # decode from trained checkpoints (invisible at zero-init)
    attn_out = (ctx.reshape(b, cfg.dim)
                @ lp["attn"]["out"]["kernel"].astype(dtype)
                + lp["attn"]["out"]["bias"].astype(dtype))
    x = x + attn_out

    h = _ln(x, lp["ff_norm"], dtype)
    # biases match training's GEGLUFeedForward (nn.Dense defaults /
    # dalle-pytorch's biased nn.Linear); dropping them here desyncs decode
    # from any TRAINED checkpoint (invisible at zero-init)
    wi = h @ lp["ff"]["wi"]["kernel"].astype(dtype) \
        + lp["ff"]["wi"]["bias"].astype(dtype)
    gate = h @ lp["ff"]["gate"]["kernel"].astype(dtype) \
        + lp["ff"]["gate"]["bias"].astype(dtype)
    ff = (wi * jax.nn.gelu(gate)) @ lp["ff"]["wo"]["kernel"].astype(dtype) \
        + lp["ff"]["wo"]["bias"].astype(dtype)
    return x + ff


def _apply_block(x, lp, mask_row, k_cache, v_cache, pos, cos_p, sin_p,
                 cfg: ModelConfig, dtype, vis: Optional[int] = None):
    """One cached block application: (B, dim) -> (B, dim) plus the block's
    updated (B, T, H*d) cache pair (merged minor axis — see init_cache).
    The incremental mirror of transformer.TransformerBlock. ``vis``
    statically truncates the attention's cache read (caller guarantees
    pos < vis); the full-length cache pair is still returned.

    ``pos`` is a scalar (whole batch at one position — every row's cache
    write lands on the same row index) or a (B,) vector (per-slot decode
    — each batch row scatters its write to its own position)."""
    b = x.shape[0]
    q, k, v = _qkv_rows(x, lp, cos_p, sin_p, cfg, dtype)
    if jnp.ndim(pos) == 0:
        k_cache = jax.lax.dynamic_update_index_in_dim(
            k_cache, k.reshape(b, cfg.dim).astype(k_cache.dtype), pos,
            axis=1)
        v_cache = jax.lax.dynamic_update_index_in_dim(
            v_cache, v.reshape(b, cfg.dim).astype(v_cache.dtype), pos,
            axis=1)
    else:
        rows = jnp.arange(b)
        k_cache = k_cache.at[rows, pos].set(
            k.reshape(b, cfg.dim).astype(k_cache.dtype))
        v_cache = v_cache.at[rows, pos].set(
            v.reshape(b, cfg.dim).astype(v_cache.dtype))
    end = k_cache.shape[1] if vis is None else vis
    y = _attend_and_ff(x, lp, q, k_cache[:, :end], v_cache[:, :end],
                       mask_row[..., :end], cfg, dtype)
    return y, k_cache, v_cache


def decode_step(params: Dict, cfg: ModelConfig, cache: Dict,
                input_ids: jax.Array, pos: jax.Array,
                visible: Optional[int] = None):
    """One cached decode step.

    input_ids: (B,) combined-vocabulary ids (BOS included) for position
    ``pos``; returns (logits over the FULL combined vocabulary at ``pos``,
    updated cache). Segment masking is applied (text positions only emit
    text ids, image positions image ids).

    ``pos`` is a scalar — every batch row decodes the same position, the
    lockstep ``generate_images`` path — or a (B,) int32 vector of
    PER-SLOT positions: row ``i`` embeds, masks, rotates and writes its
    cache at ``pos[i]``, so a serving engine can run requests admitted at
    different times through ONE jitted step (continuous batching). The
    per-row math is identical either way; only the index plumbing
    changes (gathered positional/mask rows, scattered cache writes).

    ``visible`` (STATIC) bounds the attention's cache read to positions
    ``[0, visible)`` — callers that know ``pos < visible`` (the bucketed
    ``generate_images``) skip streaming the dead tail of the cache, the
    dominant cost of a bandwidth-bound decode. ``None`` reads the full
    length.

    Cycle-structured schedules (the flagship's 4 weight-shared blocks
    x 16) run the body as ONE ``lax.scan`` over the repetitions — compile
    cost is the 4 unique blocks, not the 64 applications (training needed
    the same restructuring: PERF.md r2 #6, compile 237s -> 42s). Other
    schedules unroll exactly as before.
    """
    root = params["params"] if "params" in params else params
    dtype = jnp.dtype(cfg.dtype)
    b = input_ids.shape[0]
    t_total = cfg.total_seq_len
    vis = t_total if visible is None else min(visible, t_total)

    x = jnp.take(root["token_emb"], input_ids, axis=0)
    x = x + _positional_table(params, cfg)[pos]
    x = x.astype(dtype)                      # (B, dim)

    cos_t, sin_t = rotary_cos_sin(jnp.arange(t_total), cfg.head_dim,
                                  heads=cfg.heads)
    cos_p, sin_p = cos_t[pos], sin_t[pos]    # (H*d,)

    reps = _cycle_reps(cfg)
    if reps:
        cycle = cfg.shared_block_cycle
        sched = cfg.layer_schedule()
        n_body = len(sched) - (1 if cfg.final_conv_block else 0)
        tr = root["transformer"]
        blocks = dict(tr.get("cycle", {}))
        for key, val in tr.items():
            if key.startswith("block"):
                blocks[key] = val
        uid_masks = jnp.asarray(np.stack([
            zoo_attention_mask(cfg.attn_types[u % len(cfg.attn_types)],
                               cfg.text_seq_len, cfg.image_grid,
                               cfg.conv_kernel)
            for u in range(cycle)]))

        # The body cache rides the scan CARRY with ROW-granular updates:
        # XLA aliases while-loop carry buffers in place, so the
        # flagship's multi-GB cache exists ONCE (as xs/ys it
        # double-buffers the whole array — measured 2x 5 GB per k/v at
        # the 16-image decode), and each block application writes only
        # its new (B, H*d) row and reads only its own (B, T, H*d) block
        # — an earlier version rewrote a whole (cycle, B, T, H*d) rep
        # slice per position, ~4x the necessary cache traffic.
        b = x.shape[0]
        hd = cfg.dim

        def rep_body(carry, it):
            x, ck, cv = carry
            for uid in range(cycle):
                lp = blocks[f"block_{uid}"]
                q, k, v = _qkv_rows(x, lp, cos_p, sin_p, cfg, dtype)
                if jnp.ndim(pos) == 0:
                    start = (it, uid, 0, pos, 0)
                    ck = jax.lax.dynamic_update_slice(
                        ck, k.reshape(1, 1, b, 1, hd).astype(ck.dtype),
                        start)
                    cv = jax.lax.dynamic_update_slice(
                        cv, v.reshape(1, 1, b, 1, hd).astype(cv.dtype),
                        start)
                else:
                    # per-slot positions: row i writes (it, uid, i, pos[i])
                    rows = jnp.arange(b)
                    ck = ck.at[it, uid, rows, pos].set(
                        k.reshape(b, hd).astype(ck.dtype))
                    cv = cv.at[it, uid, rows, pos].set(
                        v.reshape(b, hd).astype(cv.dtype))
                k_blk = jax.lax.dynamic_slice(
                    ck, (it, uid, 0, 0, 0),
                    (1, 1, b, vis, hd)).reshape(b, vis, hd)
                v_blk = jax.lax.dynamic_slice(
                    cv, (it, uid, 0, 0, 0),
                    (1, 1, b, vis, hd)).reshape(b, vis, hd)
                y = _attend_and_ff(x, lp, q, k_blk, v_blk,
                                   uid_masks[uid][pos][..., :vis], cfg,
                                   dtype)
                # same overhang masking as training's BlockCycle: the
                # final repetition's surplus applications run but their
                # outputs are discarded
                active = it * cycle + uid < n_body
                x = jnp.where(active, y, x)
            return (x, ck, cv), None

        (x, body_k, body_v), _ = jax.lax.scan(
            rep_body, (x, cache["k_body"], cache["v_body"]),
            jnp.arange(reps))
        cache = dict(cache, k_body=body_k, v_body=body_v)
        if cfg.final_conv_block:
            mask = jnp.asarray(zoo_attention_mask(
                "conv_like", cfg.text_seq_len, cfg.image_grid,
                cfg.conv_kernel))
            x, k_new, v_new = _apply_block(
                x, blocks["block_wconv"], mask[pos], cache["k_conv"],
                cache["v_conv"], pos, cos_p, sin_p, cfg, dtype, vis=vis)
            cache = dict(cache, k_conv=k_new, v_conv=v_new)
    else:
        layers = layer_params(params, cfg)
        masks = jnp.asarray(_mask_stack(cfg))
        new_k, new_v = [], []
        for li, lp in enumerate(layers):
            x, k_cache, v_cache = _apply_block(
                x, lp, masks[li][pos], cache["k"][li], cache["v"][li],
                pos, cos_p, sin_p, cfg, dtype, vis=vis)
            new_k.append(k_cache)
            new_v.append(v_cache)
        cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    x = _ln(x, root["transformer"]["final_norm"], dtype)

    if cfg.tied_embeddings:
        table = root["token_emb"][: cfg.vocab_total].astype(dtype)
        logits = jnp.einsum("bd,vd->bv", x, table,
                            preferred_element_type=jnp.float32)
    else:
        logits = (x @ root["lm_head"]["kernel"].astype(dtype)).astype(
            jnp.float32)
    # segment vocabulary masking at decode (dalle-pytorch parity)
    is_text_pos = pos < cfg.text_seq_len
    vocab_is_text = jnp.arange(cfg.vocab_total) < cfg.vocab_text
    if jnp.ndim(pos) == 0:
        valid = jnp.where(is_text_pos, vocab_is_text, ~vocab_is_text)
        logits = jnp.where(valid[None, :], logits, NEG_INF)
    else:                          # per-slot: each row masks by ITS segment
        valid = jnp.where(is_text_pos[:, None], vocab_is_text[None, :],
                          ~vocab_is_text[None, :])
        logits = jnp.where(valid, logits, NEG_INF)
    return logits, cache


def sample_logits(rng: jax.Array, logits: jax.Array,
                  cfg: SamplingConfig) -> jax.Array:
    """Temperature / top-k / top-p sampling; (B, V) -> (B,) int32.

    ``temperature == 0`` is greedy argmax.

    The fields of ``cfg`` may be Python scalars (the lockstep
    ``generate_images`` path: knobs become compile-time constants and
    disabled stages vanish from the program) or **traced scalars** (the
    serving engine: knobs ride as runtime operands of ONE compiled
    program, so a novel temperature never triggers a recompile). Both
    paths pick the SAME element as threshold and filter with the SAME
    comparisons, so for equal knob values the sampled ids are
    value-identical (pinned by test_decode/test_serving). The one
    caveat: a static non-trivial temperature is a literal divisor XLA
    may fold into a reciprocal multiply (1 ulp off the runtime divide
    for ~3% of values — the PR-1 parity trap); at the pinned
    temperature 1.0 both forms are exact.
    """
    static = (isinstance(cfg.temperature, (int, float))
              and isinstance(cfg.top_k, int)
              and isinstance(cfg.top_p, (int, float)))
    if not static:
        return _sample_logits_traced(rng, logits, cfg.temperature,
                                     cfg.top_k, cfg.top_p)
    if cfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / cfg.temperature
    if cfg.top_k and cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set with cumulative probability >= top_p
        keep_sorted = cum - probs < cfg.top_p
        threshold = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < threshold[:, None], NEG_INF, logits)
    return jax.random.categorical(rng, logits).astype(jnp.int32)


def _sample_logits_traced(rng: jax.Array, logits: jax.Array,
                          temperature, top_k, top_p) -> jax.Array:
    """The traced-knob lowering of :func:`sample_logits`: every stage is
    computed unconditionally and enabled by ``jnp.where`` on the knob,
    so the compiled program is knob-independent. Value parity with the
    static path at equal knobs:

    - temperature: greedy runs as ``where(t == 0, argmax, sampled)``
      with a safe divisor (the discarded sampling branch must not
      divide by zero); ``x / 1.0`` is bitwise identity either way.
    - top-k: the threshold is the SAME sorted element the static path
      slices (``sorted[:, -k]`` == ``take(sorted, V - k)``), filtered
      by the same ``<`` comparison; ``k <= 0`` keeps every id.
    - top-p: same sorted-softmax threshold, gated by ``p < 1.0``.
    """
    v = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temperature = jnp.asarray(temperature, logits.dtype)
    is_greedy = temperature == 0.0
    x = logits / jnp.where(is_greedy, jnp.ones_like(temperature),
                           temperature)
    top_k = jnp.asarray(top_k, jnp.int32)
    k_eff = jnp.clip(top_k, 1, v)
    kth = jnp.take(jnp.sort(x, axis=-1), v - k_eff, axis=-1)[:, None]
    x = jnp.where((top_k > 0) & (x < kth), NEG_INF, x)
    top_p = jnp.asarray(top_p, logits.dtype)
    sorted_x = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_x, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = cum - probs < top_p
    threshold = jnp.min(
        jnp.where(keep_sorted, sorted_x, jnp.inf), axis=-1)
    x = jnp.where((top_p < 1.0) & (x < threshold[:, None]), NEG_INF, x)
    sampled = jax.random.categorical(rng, x).astype(jnp.int32)
    return jnp.where(is_greedy, greedy, sampled)


def bucket_bounds(total: int, n_buckets: int) -> List[int]:
    """Prefix-bucket upper bounds over ``total`` positions (clamped to
    [1, total] buckets). ONE definition for the lockstep scan
    (``generate_images``) and the serving engine's per-chunk visible
    choice — the two must truncate identically or their caches
    desynchronize."""
    n = max(1, min(int(n_buckets), total))
    return [round(total * (i + 1) / n) for i in range(n)]


def resolve_buckets(buckets: Optional[int], batch: int) -> int:
    """The adaptive prefix-bucket choice (``buckets=None``): each bucket
    boundary re-materializes the (B, T, H*d) cache carry, a cost that
    grows with B while the dead-tail-read savings do not — measured on
    the v5e flagship (DECODE_BENCH.json r4), B<=8 peaks at 4 buckets,
    B>=12 at 2."""
    if buckets is None:
        return 4 if batch <= 8 else 2
    return buckets


def generate_images(params: Dict, cfg: ModelConfig,
                    text_tokens: jax.Array, rng: jax.Array,
                    sampling: SamplingConfig = SamplingConfig(),
                    buckets: Optional[int] = None) -> jax.Array:
    """Sample (B, image_seq_len) VQGAN codes for the given captions.

    ``lax.scan`` over the positions — split into ``buckets`` prefix
    buckets whose attention reads statically-truncated caches (see the
    bucketing comment below; ``buckets=1`` is the single full-length
    scan). ``buckets=None`` picks by batch size: each bucket boundary
    re-materializes the (B, T, H*d) cache carry, a cost that grows with
    B while the dead-tail-read savings do not — measured on the v5e
    flagship (DECODE_BENCH.json r4): B<=8 peaks at 4 buckets
    (39.5 img/min at B=8), B=16 at 2 (44.2 img/min; 4 buckets there
    REGRESSES to 32.7). The B<=8 / B>=12 threshold interpolates the
    measured B=8/B=16 crossover. The text prefix is teacher-forced, image
    positions sample from the segment-masked logits (reference
    ``generate_images(text, temperature, top_k, top_p, use_cache=True)``,
    inference/run_inference.py:88-89).
    """
    b = text_tokens.shape[0]
    buckets = resolve_buckets(buckets, b)
    bos_id = cfg.vocab_total
    cache = init_cache(cfg, b)

    def make_step(visible):
        def step(carry, pos):
            cache, cur_input, rng = carry
            logits, cache = decode_step(params, cfg, cache, cur_input, pos,
                                        visible=visible)
            rng, sub = jax.random.split(rng)
            sampled = sample_logits(sub, logits, sampling)
            # position pos emits S_pos, which is the input at pos+1:
            # teacher-forced to the caption while pos is a text position,
            # the sampled code once pos is in the image block
            nxt = jnp.where(
                pos < cfg.text_seq_len,
                jnp.take(text_tokens,
                         jnp.minimum(pos, cfg.text_seq_len - 1), axis=1),
                sampled)
            return (cache, nxt, rng), sampled
        return step

    # Prefix bucketing: decode is bandwidth-bound on the cache read, but
    # positions in bucket [lo, hi) can only see cache rows [0, hi) — so
    # each bucket's scan attends to a statically-truncated cache instead
    # of streaming the dead tail (~1.6x less cache traffic at 4 buckets,
    # for ~bucket-count x the step-body compile).
    total = cfg.total_seq_len
    bounds = bucket_bounds(total, buckets)
    init_input = jnp.full((b,), bos_id, jnp.int32)
    carry = (cache, init_input, rng)
    pieces = []
    lo = 0
    for hi in bounds:
        if hi <= lo:
            continue
        carry, sampled = jax.lax.scan(
            make_step(hi), carry, jnp.arange(lo, hi))
        pieces.append(sampled)
        lo = hi
    sampled = jnp.concatenate(pieces, axis=0)
    # sampled[p] is the token emitted AT position p; image codes live at
    # positions text_seq_len..total; shift to (B, image_seq_len)
    codes = sampled[cfg.text_seq_len:].swapaxes(0, 1) - cfg.vocab_text
    return codes