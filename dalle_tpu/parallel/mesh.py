"""Device mesh construction.

The reference's intra-peer parallelism is 8-way torch_xla data parallelism
driven by a child process per core (``lib/training/tpu.py:23-231``). Here the
whole machine is one SPMD program over a ``jax.sharding.Mesh`` with four
axes — ``dp`` (data), ``fsdp`` (data + parameter sharding), ``tp`` (tensor),
``sp`` (sequence/ring attention) — and XLA inserts the ICI collectives that
``xm.all_reduce`` performed by hand in the reference (``tpu.py:181``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "fsdp", "tp", "sp")

# Batch is sharded over every data-like axis; dp and fsdp both consume
# examples, so the global batch must divide dp*fsdp.
BATCH_SPEC = P(("dp", "fsdp"))
# Per-shard layouts of the operands the Mosaic kernels see (per_shard):
# the (B, T, H*d) q/k/v/context of attention, whole heads' lanes split over
# tp; (B, T, dim) block activations are row-wise work, so the token axis
# may stay split over sp.
LANES_SPEC = P(("dp", "fsdp"), None, "tp")
TOKENS_SPEC = P(("dp", "fsdp"), "sp", None)


def make_mesh(dp: int = -1, fsdp: int = 1, tp: int = 1, sp: int = 1,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a (dp, fsdp, tp, sp) mesh over the given (default: all) devices.

    ``dp=-1`` absorbs all devices not claimed by the other axes.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    rest = fsdp * tp * sp
    if dp == -1:
        if n % rest:
            raise ValueError(f"{n} devices not divisible by fsdp*tp*sp={rest}")
        dp = n // rest
    if dp * rest != n:
        raise ValueError(
            f"mesh {dp}x{fsdp}x{tp}x{sp} != device count {n}")
    arr = np.asarray(devices).reshape(dp, fsdp, tp, sp)
    return Mesh(arr, AXES)


def unbound_axes(mesh: Mesh):
    """``(mesh, axes)`` for a ``shard_map`` opened at this point of a trace:
    the axes of ``mesh`` that the tracing context has not made manual yet.

    ``training/steps._accumulate_grads`` runs the model inside a
    ``shard_map`` manual over ``dp``; a ``shard_map`` nested in it can split
    its operands only over the other axes, and has to name the context's
    own (abstract) mesh. Outside any manual context this is the mesh
    itself and all of its axes.
    """
    ctx = jax.sharding.get_abstract_mesh()
    manual = ctx.manual_axes
    if not manual:
        return mesh, tuple(mesh.axis_names)
    return ctx, tuple(a for a in mesh.axis_names if a not in manual)


def _spec_over(spec: P, axes) -> P:
    """``spec`` with every mesh axis outside ``axes`` dropped."""
    def keep(entry):
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(a for a in names if a in axes)
        return names if len(names) > 1 else (names[0] if names else None)
    return P(*(keep(e) for e in spec))


def shard_map_unbound(body, mesh: Mesh, in_specs, out_specs, check_vma):
    """``jax.shard_map`` manual over :func:`unbound_axes` of ``mesh``: the
    specs, written for the whole mesh, lose the axes that are manual
    already (along those an operand is this shard's block as it stands).

    Nested like that, a body cannot call ``lax.axis_index``: jax 0.9.0
    lowers it as if the outer axes were not manual (the verifier refuses
    "axis already bound by a parent"). A body that needs its position
    takes it as an operand, a slice of ``arange`` split over that axis.
    """
    mesh, axes = unbound_axes(mesh)
    if not axes:
        # every axis is manual already (a pure-dp mesh inside the gradient
        # accumulation): the operands are this device's own as they stand
        return body

    def over(specs):
        return jax.tree.map(lambda s: _spec_over(s, axes), specs,
                            is_leaf=lambda s: isinstance(s, P))
    return jax.shard_map(body, mesh=mesh, in_specs=over(in_specs),
                         out_specs=over(out_specs), axis_names=set(axes),
                         check_vma=check_vma)


def sum_over_manual_data_axes(x):
    """``x`` summed over the data axes that are manual in the tracing
    context: what one shard of a batch contributes, made the whole
    batch's. Outside such a context (one device, GSPMD) it is ``x``."""
    manual = jax.sharding.get_abstract_mesh().manual_axes
    axes = tuple(a for a in ("dp", "fsdp") if a in manual)
    return jax.lax.psum(x, axes) if axes else x


def per_shard(fn, mesh: Optional[Mesh], in_specs, out_specs,
              scope: Optional[str] = None):
    """``fn`` run once per device on its own shard of the operands.

    GSPMD cannot partition a Mosaic kernel (the lowering raises "Mosaic
    kernels cannot be automatically partitioned" for any jit that spans
    more than one device), so every Pallas call site goes through here:
    ``shard_map`` manual over ALL mesh axes, which is the one context the
    lowering accepts; where the call sits inside a ``shard_map`` already
    (the gradient accumulation, manual over ``dp``), over all that are
    left (:func:`shard_map_unbound`; the context is read here, so build the
    wrapper where it is called). On one device (or with no mesh) it is
    ``fn`` itself.
    Replication is not type-checked (``check_vma=False``: ``pallas_call``
    carries no varying-axes annotation); the per-kernel parity tests on
    the 8-device mesh are the check.

    XLA names a Mosaic custom call after the innermost name-stack
    component at the ``pallas_call``: the calling flax module's name on one
    device (``attn``, ``ff``, ``attn_norm``), but ``shard_map`` once this
    wrapper sits between. ``scope`` — the call site's own name — is opened
    again inside the body, so a kernel keeps its name on a mesh and the
    trace reduction finds it on four chips as on one.
    """
    if mesh is None or mesh.size == 1:
        return fn
    body = fn
    if scope is not None:
        def body(*args):
            with jax.named_scope(scope):
                return fn(*args)
    return shard_map_unbound(body, mesh, in_specs, out_specs,
                             check_vma=False)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, BATCH_SPEC)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
