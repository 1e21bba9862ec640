"""LAMB with block-wise 8-bit quantized moment state.

Capability parity with the reference's ``CPULAMB8Bit``
(``lib/training/lamb_8bit.py:13-249`` of learning-at-home/dalle): first and
second moments are stored block-quantized to uint8 (block 4096), tensors
smaller than ``min_8bit_size`` keep dense fp32 state (``lamb_8bit.py:49,103``),
the global-norm clip runs before the moment update (``:84-88``), and the
trust ratio clamps the weight norm (``:149-158``). Update math is shared
with :func:`dalle_tpu.optim.lamb.lamb` — the 8-bit variant must follow the
identical trajectory up to quantization error.

Differences by design (TPU-native): state lives on device (sharded over the
mesh) instead of host RAM, so the reference's 2^24-element chunking
(``lamb_8bit.py:202-249``) and CPU offload are unnecessary; quantize/
dequantize are XLA ops (Pallas-fusable) instead of bitsandbytes CUDA/C++
kernels. The first moment uses the signed dynamic codebook, the second
(non-negative) the unsigned one, as in the 8-bit optimizers paper.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

from dalle_tpu.config import OptimizerConfig
from dalle_tpu.ops.quant import (
    DEFAULT_BLOCK,
    Quantized,
    dequantize_blockwise,
    quantize_blockwise,
)
from dalle_tpu.optim.lamb import (
    ScalarOrSchedule,
    default_stacked_mask,
    default_wd_mask,
    global_norm,
    lamb_leaf_update,
    make_lr_schedule,
)


class Lamb8bitState(NamedTuple):
    count: jax.Array
    mu: Any   # per-leaf: Quantized (large tensors) or fp32 array
    nu: Any


def _is_q(x) -> bool:
    return isinstance(x, Quantized)


def lamb8bit(learning_rate: ScalarOrSchedule,
             b1: float = 0.9,
             b2: float = 0.96,
             eps: float = 1e-6,
             weight_decay: float = 0.045,
             clamp_value: float = 10000.0,
             max_grad_norm: Optional[float] = 4.0,
             block_size: int = DEFAULT_BLOCK,
             min_8bit_size: int = 65536,
             wd_mask_fn: Callable[[Any], Any] = default_wd_mask,
             stacked_reps: Optional[int] = None,
             stacked_experts: Optional[int] = None,
             mesh=None,
             ) -> optax.GradientTransformation:
    """``mesh``: the device mesh the train state is sharded over; the
    quantize kernel then runs per shard of it (ops/quant.py)."""

    def _quantize_moment(x: jax.Array, signed: bool):
        if x.size >= min_8bit_size:
            return quantize_blockwise(x, block_size, signed=signed,
                                      mesh=mesh)
        return x

    def _dequantize_moment(m) -> jax.Array:
        return dequantize_blockwise(m) if _is_q(m) else m

    def init_fn(params):
        def init_leaf(signed):
            def f(p):
                z = jnp.zeros(p.shape, jnp.float32)
                return _quantize_moment(z, signed)
            return f
        return Lamb8bitState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(init_leaf(True), params),
            nu=jax.tree.map(init_leaf(False), params))

    def update_fn(updates, state, params):
        if params is None:
            raise ValueError("lamb8bit requires params")
        treedef = jax.tree.structure(params)
        p_leaves = jax.tree.leaves(params)
        g_leaves = treedef.flatten_up_to(updates)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)
        d_leaves = treedef.flatten_up_to(wd_mask_fn(params))
        s_leaves = treedef.flatten_up_to(
            default_stacked_mask(params, stacked_reps, stacked_experts))

        g_leaves = [g.astype(jnp.float32) for g in g_leaves]
        if max_grad_norm is not None:
            gnorm = global_norm(g_leaves)
            scale = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-12))
            g_leaves = [g * scale for g in g_leaves]

        lr = learning_rate(state.count) if callable(learning_rate) \
            else learning_rate

        new_updates, new_mu, new_nu = [], [], []
        for p, g, m_s, v_s, decay, stacked in zip(
                p_leaves, g_leaves, m_leaves, v_leaves, d_leaves, s_leaves):
            m = b1 * _dequantize_moment(m_s) + (1 - b1) * g
            v = b2 * _dequantize_moment(v_s) + (1 - b2) * g * g
            new_updates.append(lamb_leaf_update(
                p, m, v, decay, lr, eps=eps, weight_decay=weight_decay,
                clamp_value=clamp_value, stacked=stacked))
            new_mu.append(_quantize_moment(m, True) if _is_q(m_s) else m)
            new_nu.append(_quantize_moment(v, False) if _is_q(v_s) else v)

        return (jax.tree.unflatten(treedef, new_updates),
                Lamb8bitState(state.count + 1,
                              jax.tree.unflatten(treedef, new_mu),
                              jax.tree.unflatten(treedef, new_nu)))

    return optax.GradientTransformation(init_fn, update_fn)


def make_optimizer_8bit(cfg: OptimizerConfig,
                        mesh=None) -> optax.GradientTransformation:
    return lamb8bit(
        learning_rate=make_lr_schedule(cfg),
        b1=cfg.beta1, b2=cfg.beta2, eps=cfg.eps,
        weight_decay=cfg.weight_decay, clamp_value=cfg.clamp_value,
        max_grad_norm=cfg.max_grad_norm, block_size=cfg.block_size,
        min_8bit_size=cfg.min_8bit_size, stacked_reps=cfg.stacked_reps,
        stacked_experts=cfg.stacked_experts, mesh=mesh)


def optimizer_state_bytes(state) -> int:
    """Actual bytes held by optimizer state (uint8 codes count as 1B)."""
    total = 0
    for leaf in jax.tree.leaves(state):
        total += leaf.size * leaf.dtype.itemsize
    return total
