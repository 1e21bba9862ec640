"""LAMB with built-in global-norm clipping, optax-style.

Numerics follow the reference's optimizer exactly (its fp32 path):
``lib/training/clipped_lamb.py:5-14`` (LAMB + global clip fused, so the
collaborative wrapper can bypass external clipping) and
``lib/training/lamb_8bit.py:84-88,135-158`` (clip before moments; no bias
correction / debias=False; trust ratio = clamp(||w||, max=clamp_value) /
||m/(sqrt(v)+eps) + wd*w||, 1.0 where either norm is zero). Weight-decay
exclusion of bias/LayerNorm parameters (reference ``task.py:144-151``) is a
``wd_mask`` predicate over parameter paths.

The 8-bit block-quantized variant with identical math but uint8 moment state
lives in :mod:`dalle_tpu.optim.lamb8bit`.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

from dalle_tpu.config import OptimizerConfig

ScalarOrSchedule = Union[float, Callable[[jax.Array], jax.Array]]


class LambState(NamedTuple):
    count: jax.Array
    mu: Any
    nu: Any


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def default_wd_mask(params) -> Any:
    """True where weight decay applies: exclude biases and (layer)norm scales
    (reference task.py:144-151 excludes ["bias", "LayerNorm.weight"])."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, _ in flat:
        keys = [getattr(p, "key", str(p)).lower() for p in path]
        joined = "/".join(str(k) for k in keys)
        decay = not ("bias" in joined or "norm" in joined
                     or "scale" in joined)
        out.append(decay)
    return jax.tree_util.tree_unflatten(treedef, out)


def default_stacked_mask(params, reps: Optional[int] = None,
                         experts: Optional[int] = None) -> Any:
    """How many LEADING axes of each leaf hold independent weights (0 for
    an ordinary leaf). LAMB's per-tensor trust ratio must then be computed
    PER SLICE so a stacked model optimizes identically to its unrolled
    equivalent — one shared ratio across 16 independent layers, or 8
    independent experts, would silently change convergence dynamics vs
    the model the stacking merely re-stages. Two kinds of stacking:

    - dense_scan's per-iteration leaves (transformer.py: scan with
      ``variable_axes={"params": 0}``): leaves under the scanned ``cycle``
      whose rank exceeds their kind's canonical rank (kernel 2; bias/scale
      1) carry a leading scan-reps axis of independent layers;
    - an expert layer's leaves (models/sparse_lm.py: ``.../experts/<name>``
      of rank 3): the experts held on the leading axis, one trust ratio
      per expert (per layer: each layer's leaf is its own).

    ``reps`` / ``experts`` are the config-derived stacked-axis sizes
    (``<model config>.optimizer_stacking()``, threaded through
    ``OptimizerConfig.stacked_reps`` / ``stacked_experts`` by the task
    wiring): 0 means the model has NO such leaves (every leaf gets the
    ordinary per-tensor ratio regardless of its name), and a positive
    value additionally requires the leading axis to equal it — so a
    future rank-3 kernel or odd-rank param under those scopes cannot
    silently opt into per-slice ratios (ADVICE r4). ``None`` keeps the
    name+rank inference for callers without model context."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    out = []
    for path, leaf in flat:
        keys = [getattr(p, "key", str(p)).lower() for p in path]
        canonical = 2 if keys and keys[-1] == "kernel" else 1
        stacked = "cycle" in keys and leaf.ndim > canonical
        if reps is not None:
            stacked = (stacked and reps > 0
                       and leaf.ndim == canonical + 1
                       and leaf.shape[0] == reps)
        by_expert = "experts" in keys[:-1] and leaf.ndim == 3
        if experts is not None:
            by_expert = (by_expert and experts > 0
                         and leaf.shape[0] == experts)
        out.append(int(stacked) + int(by_expert))
    return jax.tree_util.tree_unflatten(treedef, out)


def lamb_leaf_update(p: jax.Array, m: jax.Array, v: jax.Array,
                     decay, lr, *, eps: float, weight_decay: float,
                     clamp_value: float, stacked: int = 0) -> jax.Array:
    """The shared per-tensor LAMB update (used by both the fp32 and 8-bit
    optimizers so their trajectories agree up to moment quantization):
    adam_step = m/(sqrt(v)+eps) + wd*p; trust = clamp(||p||, clamp_value) /
    ||adam_step|| (1.0 where either norm is 0); update = -lr*trust*adam_step.
    Matches reference lamb_8bit.py:135-158 (debias=False).

    ``stacked`` (see default_stacked_mask): that many leading axes hold
    independent layers' or experts' weights — norms and trust ratios are
    computed per slice so the update equals the unrolled model's."""
    p32 = p.astype(jnp.float32)
    adam_step = m / (jnp.sqrt(v) + eps)
    if weight_decay:
        adam_step = adam_step + jnp.where(decay, weight_decay, 0.0) * p32
    axes = tuple(range(int(stacked), p32.ndim)) if stacked else None
    wnorm = jnp.minimum(
        jnp.sqrt(jnp.sum(p32 * p32, axis=axes, keepdims=bool(stacked))),
        clamp_value)
    anorm = jnp.sqrt(jnp.sum(adam_step * adam_step, axis=axes,
                             keepdims=bool(stacked)))
    trust = jnp.where((wnorm > 0) & (anorm > 0),
                      wnorm / (anorm + 1e-12), 1.0)
    return (-lr * trust * adam_step).astype(p.dtype)


def lamb(learning_rate: ScalarOrSchedule,
         b1: float = 0.9,
         b2: float = 0.96,
         eps: float = 1e-6,
         weight_decay: float = 0.045,
         clamp_value: float = 10000.0,
         max_grad_norm: Optional[float] = 4.0,
         wd_mask_fn: Callable[[Any], Any] = default_wd_mask,
         stacked_reps: Optional[int] = None,
         stacked_experts: Optional[int] = None,
         ) -> optax.GradientTransformation:

    def init_fn(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return LambState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree.map(zeros, params),
            nu=jax.tree.map(zeros, params))

    def update_fn(updates, state, params):
        if params is None:
            raise ValueError("lamb requires params")
        updates = jax.tree.map(lambda g: g.astype(jnp.float32), updates)

        if max_grad_norm is not None:
            gnorm = global_norm(updates)
            scale = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-12))
            updates = jax.tree.map(lambda g: g * scale, updates)

        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                          state.mu, updates)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          state.nu, updates)

        lr = learning_rate(state.count) if callable(learning_rate) \
            else learning_rate
        wd_mask = wd_mask_fn(params)
        stacked_mask = default_stacked_mask(params, stacked_reps,
                                            stacked_experts)

        def leaf_update(p, m, v, decay, stacked):
            return lamb_leaf_update(
                p, m, v, decay, lr, eps=eps, weight_decay=weight_decay,
                clamp_value=clamp_value, stacked=stacked)

        new_updates = jax.tree.map(leaf_update, params, mu, nu, wd_mask,
                                   stacked_mask)
        return new_updates, LambState(state.count + 1, mu, nu)

    return optax.GradientTransformation(init_fn, update_fn)


def make_lr_schedule(cfg: OptimizerConfig) -> Callable[[jax.Array], jax.Array]:
    """Linear warmup to peak then linear decay to zero (reference uses
    transformers' linear schedule: warmup 3125 of 31250, task.py:163-165)."""
    return optax.join_schedules(
        schedules=[
            optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps),
            optax.linear_schedule(
                cfg.learning_rate, 0.0,
                max(cfg.total_steps - cfg.warmup_steps, 1)),
        ],
        boundaries=[cfg.warmup_steps])


def make_optimizer_fp32(cfg: OptimizerConfig) -> optax.GradientTransformation:
    """The reference's fp32 optimizer variant (clipped LAMB + linear
    schedule, parity with clipped_lamb.py). The config-driven entry point
    dalle_tpu.optim.make_optimizer dispatches on cfg.state_bits."""
    return lamb(
        learning_rate=make_lr_schedule(cfg),
        b1=cfg.beta1, b2=cfg.beta2, eps=cfg.eps,
        weight_decay=cfg.weight_decay, clamp_value=cfg.clamp_value,
        max_grad_norm=cfg.max_grad_norm, stacked_reps=cfg.stacked_reps,
        stacked_experts=cfg.stacked_experts)
