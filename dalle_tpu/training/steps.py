"""Jitted training steps with the collaborative seam.

Three entry points, mirroring the host-loop seam of the reference's TPU path
(``run_trainer_tpu.py:78-91``: accumulate on device -> hand grads to the
swarm -> apply the averaged step):

- :func:`make_train_step`     — fused local step (grad + optimizer update);
  the single-peer / non-collaborative path.
- :func:`make_grad_step`      — forward/backward only, returns the local
  mean gradient without touching optimizer state; what a peer runs while the
  swarm accumulates toward ``target_batch_size``. Sample-count weighting
  across peers is the averager's job (it weights each peer's contribution
  by its accumulated samples, as hivemind's GradientAverager does).
- :func:`make_apply_step`     — applies (averaged) gradients via the
  optimizer; what runs once per swarm epoch.

Gradient accumulation is a ``lax.scan`` over microbatches (the reference
loops in Python per core, ``lib/training/tpu.py:119-126``). All steps donate
their state buffers so XLA updates parameters in place.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Dict, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

logger = logging.getLogger(__name__)


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any

    @classmethod
    def create(cls, params, tx: optax.GradientTransformation) -> "TrainState":
        return cls(step=jnp.zeros([], jnp.int32), params=params,
                   opt_state=tx.init(params))


def _loss_fn(model, params, batch):
    cfg = getattr(model, "cfg", None)
    if cfg is not None and getattr(cfg, "param_cast_hoist", False):
        # Hoist the f32->activation-dtype parameter casts to the TOP of
        # the loss: every in-block cast (flax dtype promotion) becomes a
        # no-op, so nothing re-casts inside remat replays (4.1% of the r3
        # flagship profile), and the weight-shared scan's gradient carry
        # accumulates in the ACTIVATION dtype — the cast's VJP converts
        # the summed cotangent back to f32 once per microbatch. Master
        # params, LAMB, and the cross-microbatch accumulator stay f32;
        # only in-scan gradient accumulation narrows (config.py
        # param_cast_hoist documents the measured trade).
        adt = jnp.dtype(cfg.dtype)
        params = jax.tree.map(
            lambda p: p.astype(adt)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
    loss, aux = model.apply(params, batch["text"], batch["image"],
                            loss_mask=batch.get("mask"))
    return loss, aux


GRAD_ACCUMULATE_SCOPE = "grad_accumulate"


def _reduces_once(mesh) -> bool:
    """Whether :func:`_accumulate_grads` takes the ``dp`` reduction out of
    the scans: ``dp > 1``, and at most one other mesh axis larger than 1.

    With two more (dp2 x fsdp2 x tp2) XLA's SPMD partitioner ABORTS the
    process on some programs once ``dp`` is manual: a gather whose
    operand is partly replicated over the automatic axes (the embedding
    lookup of a tp-sharded table with the hidden axis over fsdp) fails a
    ``Check`` in ``spmd_partitioner_util.cc`` (``ExpandDeviceGroupsWithIota``;
    jaxlib 0.9.0 on the CPU and libtpu 0.0.34 compiling for a v5e alike).
    One automatic axis cannot be partly replicated, so there the path is
    safe; such a mesh keeps the partitioner's in-loop reduction.
    """
    if mesh is None or mesh.shape["dp"] == 1:
        return False
    return sum(n > 1 for a, n in mesh.shape.items() if a != "dp") <= 1


def grad_reduction_plan(mesh) -> str:
    """Where the gradient is summed over the mesh's ``dp`` axis, in words:
    what :func:`_accumulate_grads` logs once when a step is traced and
    the ``setup/warmup`` row of the ``train`` plane carries."""
    if mesh is None or mesh.size == 1:
        return "single device: none"
    dp = mesh.shape["dp"]
    if dp == 1:
        return "dp=1: none"
    if _reduces_once(mesh):
        return f"over dp={dp}: once per step"
    return f"over dp={dp}: inside the scans, where the partitioner puts it"


@functools.lru_cache(maxsize=None)
def _log_reduction_plan(plan: str) -> None:
    logger.info("gradient reduction %s", plan)


def _accumulate_grads(model, params, batch, accum_steps: int):
    """Mean loss/grads over the batch, ``accum_steps`` microbatches at a
    time, reduced over the mesh's ``dp`` axis ONCE.

    Left to GSPMD, every weight-gradient matmul contracts over the
    dp-sharded batch axis, its result is a partial sum, and the
    partitioner reduces it where it is made: inside the backward body of
    the weight-shared layer scan, every iteration of every microbatch
    (the flagship on four chips: ~270 all-reduces and 18 GB a chip a step
    for a 0.5 GB gradient). So on a mesh with ``dp > 1``
    (:func:`_reduces_once`) the whole accumulation runs under a
    ``shard_map`` manual over ``dp``: each shard accumulates the gradient
    of its own samples in f32, and one ``psum`` follows the scan. An
    ``fsdp``/``tp``/``sp`` axis larger than 1 stays the partitioner's.
    """
    mesh = getattr(model, "mesh", None)
    _log_reduction_plan(grad_reduction_plan(mesh))
    if not _reduces_once(mesh):
        return _shard_grads(model, params, batch, accum_steps)
    dp = mesh.shape["dp"]
    if accum_steps > 1 and "mask" in batch:
        # a masked loss is normalised per microbatch, so which samples
        # share one matters: deal the batch so that the shards' i-th
        # microbatches together are the batch's i-th microbatch, as on
        # one device (tokens only, once a step, outside the scan)
        def deal(x):
            n = x.shape[0] // (accum_steps * dp)
            x = x.reshape(accum_steps, dp, n, *x.shape[1:])
            return x.swapaxes(0, 1).reshape(-1, *x.shape[3:])
        batch = jax.tree.map(deal, batch)

    def shard(params, batch):
        # DALLE normalises by the whole microbatch (its denominators are
        # summed over dp), so a shard's loss and gradient are its share
        # of the mean and the shares add up
        return jax.lax.psum(
            _shard_grads(model, params, batch, accum_steps), "dp")

    # axes of size 1 split nothing: made manual with dp, they leave a
    # pure-dp mesh nothing to nest a shard_map for (parallel/mesh.per_shard
    # then calls its kernel as on one device: 92 fewer shard_maps to trace,
    # differentiate and lower in the flagship's step)
    manual = {a for a, n in mesh.shape.items() if a == "dp" or n == 1}
    return jax.shard_map(shard, mesh=mesh, in_specs=(P(), P("dp")),
                         out_specs=P(), axis_names=manual,
                         check_vma=False)(params, batch)


def _shard_grads(model, params, batch, accum_steps: int):
    """Mean loss/grads of the batch in hand via lax.scan over microbatches."""
    if accum_steps <= 1:
        (loss, aux), grads = jax.value_and_grad(
            functools.partial(_loss_fn, model), has_aux=True)(params, batch)
        return loss, aux, grads

    def split(x):
        return x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:])

    micro = jax.tree.map(split, batch)
    grad_fn = jax.value_and_grad(
        functools.partial(_loss_fn, model), has_aux=True)

    def body(carry, mb):
        g_acc, loss_acc = carry
        (loss, aux), g = grad_fn(params, mb)
        # a device scope of its own (models/dalle.py names the others):
        # the accumulation's add shows apart from the backward pass
        with jax.named_scope(GRAD_ACCUMULATE_SCOPE):
            g_acc = jax.tree.map(jnp.add, g_acc, g)
        # the model's own aux (its losses, and whatever else it counts a
        # step) leaves the scan a microbatch at a time: the accumulation
        # names none of its entries
        return (g_acc, loss_acc + loss), aux

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (grads, loss), aux = jax.lax.scan(
        body, (g0, jnp.zeros([], jnp.float32)), micro)
    aux = jax.tree.map(lambda a: jnp.sum(a, axis=0), aux)
    inv = 1.0 / accum_steps
    grads = jax.tree.map(lambda g: g * inv, grads)
    aux = jax.tree.map(lambda a: a * inv, aux)
    return loss * inv, aux, grads


def make_train_step(model, tx: optax.GradientTransformation,
                    accum_steps: int = 1) -> Callable:
    """Fused step: state, batch -> new_state, metrics."""

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        loss, aux, grads = _accumulate_grads(
            model, state.params, batch, accum_steps)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = dict(aux)
        metrics["grad_norm"] = optax.global_norm(grads)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), metrics

    return train_step


def make_grad_step(model, accum_steps: int = 1) -> Callable:
    """Accumulation-only step: (params, batch) -> (grads, metrics)."""

    def grad_step(params, batch):
        loss, aux, grads = _accumulate_grads(model, params, batch,
                                             accum_steps)
        return grads, dict(aux)

    return grad_step


def make_apply_step(tx: optax.GradientTransformation) -> Callable:
    """(state, averaged_grads) -> new_state. The once-per-swarm-epoch step."""

    def apply_step(state: TrainState, grads):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state)

    return apply_step
