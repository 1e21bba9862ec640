"""Operations and bytes the algorithm needs, computed from shapes alone.

The benchmark's own arithmetic for ``mfu_pct`` and ``attn_roofline``: what
the forward and backward passes *require* — matmuls at 2 flops a
multiply-add, attention over the allowed (query, key) pairs only, backward
at twice the forward. Replays under rematerialisation, the overhanging scan
block and masked-out score tiles are work the program chose, not work the
model needs, and are not counted. ``model`` is the ``model`` group of a
configuration file.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Mapping

from benchmark.reference import attention_mask, layer_schedule

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of this kind; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add a row with its source")
    return table[device_kind]


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
