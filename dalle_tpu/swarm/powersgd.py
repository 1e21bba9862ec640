"""PowerSGD gradient compression for swarm averaging — device-side math.

Low-rank gradient compression (Vogels et al., NeurIPS 2019) as an alternate
``grad_compression`` mode. The reference's hivemind fork carries PowerSGD
as an upstream averager variant (SURVEY.md §2 component 15: "blockwise/
PowerSGD exist upstream as alternates"; §7 build plan item 6 names it for
this build); the dalle app itself ships with size-adaptive fp16/8-bit.

Algorithm, per 2D-reshapable gradient M (m x n), rank r:

1. error feedback: ``M += e`` (the residual from last round);
2. ``P = M @ Q`` with the epoch-seeded projection Q (n x r);
3. **average P across the group** (the existing butterfly all-reduce);
4. orthogonalize the averaged P (modified Gram-Schmidt) — every peer runs
   the same deterministic step on the same averaged bytes, so all peers
   hold the identical orthonormal basis;
5. ``Q = M^T @ P_orth`` and **average Q across the group**;
6. reconstruct ``G = P_orth @ Q^T``; store ``e = M - G`` locally.

**Where the work happens.** All O(m*n*r) math — the P/Q projections, the
reconstruction, and the error-feedback update — runs as jitted device ops
(the north star names PowerSGD "reimplemented as XLA/Pallas kernels");
the error-feedback and M caches are device arrays, not host
RAM. Only the rank-r factors (r*(m+n) floats per tensor, ~128x smaller
than the gradients at the flagship's 1024x4096 blocks) cross to the host
for the wire. Gram-Schmidt is the one exception, and it runs on the HOST
by default (``host_orthogonalize=True``): cross-peer basis agreement
needs every member to orthogonalize the identical averaged-P bytes
identically, and device MGS only guarantees that on one homogeneous XLA
backend build — a volunteer swarm (v4/v5e/CPU peers, mixed jax versions)
is exactly where that assumption breaks, and divergent bases silently
corrupt the reconstruction on every peer. Host MGS in plain IEEE f32
loop order is bit-identical across peers and costs O(m*r^2) on a rank-4
factor — noise next to the wire round-trip. The butterfly's owner path
makes the averaged-P input bytes byte-identical across survivors
(swarm/allreduce.py). ``host_orthogonalize=False`` keeps the whole phase
on device for fleets pinned to one backend build.

Cross-peer correctness hinges on every peer holding the identical Q basis
in phase 2 and the identical averaged-P bytes in phase 4. Two design
choices guarantee the first by construction under elastic membership:

- Q is seeded deterministically from ``(seed, tensor index, epoch)`` and
  **never** warm-started from a previous round's average — a peer that
  joins at epoch N derives exactly the veterans' Q without communication,
  and a peer that missed a round cannot drift. (The PowerSGD paper's
  warm start is a per-round quality optimization; under swarm elasticity
  it is a cross-peer consistency hazard, so it is deliberately absent.
  Error feedback recovers the approximation quality over rounds.)
- The butterfly all-reduce reports whether the round was *complete* (every
  expected chunk arrived); an incomplete factor round means this peer's
  averaged bytes may differ from other survivors', so the caller falls
  back to its local gradients for the epoch (exactly the "divergent peer
  falls out of the round" elasticity the plain codecs have) instead of
  reconstructing from mismatched bases.

Tensors too small to win from rank-r factorization travel uncompressed
through the same all-reduce rounds (appended to the Q phase).

Trust (r16): the factor rounds ride the same butterfly as the gradient
rounds and the same verified-aggregation machinery under their own
prefixes (``{run}_grads_p`` / ``_q``) — a hostile factor-part owner serving wrong averaged-P bytes
(which every peer would then orthogonalize into a corrupted shared
basis) is convicted by transcript replay exactly like a gradient-part
owner, and the conviction gossips as a proof-carrying receipt
(swarm/audit.py, CHAOS.md "Round repair"). Factor rounds are REPAIRED
as well: the conviction's ``honest - served`` correction is queued
under the phase's own prefix and the optimizer's reduce callback drains
it into the averaged factor bytes before reconstruction — in projection
space, where the correction actually lives, never scattered into the
gradient accumulator. Where no
repair plane is wired (a multi-host slice) the blast radius of one wrong
factor round stays this epoch's reconstruction — the same bound the
:class:`IncompleteRound` fallback already accepts.

Compression: a (m x n) tensor costs r*(m+n) floats on the wire instead of
m*n — at the flagship's 1024x1024 blocks and rank 4 that is 128x less
gradient traffic per round, at the cost of a rank-r approximation whose
error re-enters via feedback next round.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: tensors compress only if rank-r factors are at most this fraction of
#: the raw payload (hivemind's min_compression_rate idea)
MIN_COMPRESSION_RATIO = 0.5


class IncompleteRound(Exception):
    """A factor all-reduce did not receive every expected chunk: this
    peer's averaged bytes may differ from other survivors', so the caller
    must not reconstruct from them (mismatched orthogonal bases corrupt
    the gradients on every peer)."""


@dataclasses.dataclass
class _TensorPlan:
    index: int                   # position in the gradient leaf list
    shape: Tuple[int, ...]       # original shape
    m: int                       # rows after 2D reshape
    n: int                       # cols after 2D reshape


def _as_matrix(shape: Sequence[int]) -> Tuple[int, int]:
    """Collapse a >=2D shape to (leading, trailing) — first axis vs rest,
    the standard PowerSGD matricization."""
    m = int(shape[0])
    n = 1
    for s in shape[1:]:
        n *= int(s)
    return m, n


#: a column whose post-projection residual is below this fraction of its
#: original norm is (numerically) linearly dependent on the earlier basis:
#: it must be ZEROED, not normalized — normalizing pure cancellation noise
#: into a unit vector with large overlap with the earlier columns makes
#: P_orth non-orthogonal and the reconstruction over-counts the gradient
#: by up to the rank (this bites immediately on rank-deficient averaged
#: Ps, e.g. near-constant gradients). A zero column simply lowers the
#: effective rank for the round; error feedback recovers the residual.
MGS_RELATIVE_TOL = 1e-4


def orthogonalize(p: np.ndarray, rel_tol: float = MGS_RELATIVE_TOL
                  ) -> np.ndarray:
    """Host-side modified Gram-Schmidt: plain IEEE f32 loop order,
    bit-identical across x86 peers for identical input bytes. Used for
    the epoch-seeded Q init and the ``host_orthogonalize`` mode.
    Numerically dependent columns come back zero (see MGS_RELATIVE_TOL)."""
    p = np.array(p, np.float32, copy=True)
    for i in range(p.shape[1]):
        col = p[:, i]
        orig = float(np.linalg.norm(col))
        for j in range(i):
            col -= (col @ p[:, j]) * p[:, j]
        norm = float(np.linalg.norm(col))
        if norm > rel_tol * orig:
            p[:, i] = col / norm
        else:
            p[:, i] = 0.0
    return p


def _orthogonalize_dev(p: jax.Array, rel_tol: float = MGS_RELATIVE_TOL
                       ) -> jax.Array:
    """Device MGS, unrolled over the (tiny, static) rank columns; same
    dependent-column zeroing as the host version."""
    cols: List[jax.Array] = []
    for i in range(p.shape[1]):
        c = p[:, i]
        orig = jnp.linalg.norm(c)
        for q in cols:
            c = c - jnp.dot(c, q) * q
        norm = jnp.linalg.norm(c)
        keep = norm > rel_tol * orig
        safe = jnp.where(keep, norm, 1.0)
        cols.append(jnp.where(keep, c / safe, 0.0))
    return jnp.stack(cols, axis=1)


# The three device phases. Lists of arrays are pytrees, so one jitted
# program covers the whole planned gradient set; XLA fuses the per-tensor
# error add into the projection matmul.

@jax.jit
def _dev_phase1(mats, errs, qs):
    mats_e = [m.astype(jnp.float32) + e for m, e in zip(mats, errs)]
    ps = [me @ q for me, q in zip(mats_e, qs)]
    return mats_e, ps


@jax.jit
def _dev_phase2(mats_e, p_avgs):
    p_orths = [_orthogonalize_dev(p) for p in p_avgs]
    qs = [me.T @ po for me, po in zip(mats_e, p_orths)]
    return p_orths, qs


@jax.jit
def _dev_phase2_preorth(mats_e, p_orths):
    return [me.T @ po for me, po in zip(mats_e, p_orths)]


@jax.jit
def _dev_reconstruct(mats_e, p_orths, q_avgs):
    approx = [po @ qa.T for po, qa in zip(p_orths, q_avgs)]
    errs = [me - ap for me, ap in zip(mats_e, approx)]
    return approx, errs


class PowerSGDCompressor:
    """Per-peer PowerSGD state: device-resident error feedback + the
    in-flight M caches. Qs are epoch-seeded, NOT warm-started (see the
    module docstring's elasticity argument), so there is no cross-epoch
    basis state to keep.

    One instance per CollaborativeOptimizer; its lifetime spans epochs so
    error feedback accumulates.
    """

    def __init__(self, rank: int = 4, seed: int = 0,
                 min_ratio: float = MIN_COMPRESSION_RATIO,
                 host_orthogonalize: bool = True,
                 keep_factors_on_device: bool = False):
        self.rank = rank
        self.seed = seed
        self.min_ratio = min_ratio
        self.host_orthogonalize = host_orthogonalize
        # Hand the P/Q factors to the wire as DEVICE arrays instead of
        # host-pulling them (the device wire codec consumes them where
        # they live — swarm/device_codec.py). Single-process peers only:
        # on sharded slices host_global is the collective that makes the
        # factors global, and it must keep running in lockstep.
        self.keep_factors_on_device = keep_factors_on_device
        self._errors: Dict[int, jax.Array] = {}
        self._mat_cache: Dict[int, jax.Array] = {}
        self._p_orth: Dict[int, jax.Array] = {}

    # -- planning ---------------------------------------------------------

    def plan(self, leaves: Sequence[Any]) -> List[_TensorPlan]:
        plans = []
        for i, leaf in enumerate(leaves):
            if leaf.ndim < 2:
                continue
            m, n = _as_matrix(leaf.shape)
            if min(m, n) < self.rank:
                continue  # factorization cannot even express the tensor
            if self.rank * (m + n) > self.min_ratio * m * n:
                continue
            plans.append(_TensorPlan(i, tuple(leaf.shape), m, n))
        return plans

    def _q_for(self, plan: _TensorPlan, epoch: int) -> np.ndarray:
        # seeded by (seed, tensor index, epoch) ONLY — every peer,
        # including one that just joined, derives the identical Q
        rng = np.random.RandomState(
            (self.seed * 1_000_003 + plan.index * 7919 + epoch)
            % (2 ** 31 - 1))
        return orthogonalize(
            rng.randn(plan.n, self.rank).astype(np.float32))

    # -- the two communication phases ------------------------------------

    def phase1_ps(self, leaves: Sequence[Any],
                  plans: List[_TensorPlan],
                  epoch: int = 0) -> List[np.ndarray]:
        """Error-compensated P factors to be averaged across the group.
        Projections run jitted on device; only the (m x r) factors are
        pulled to the host for the wire."""
        mats = [jnp.asarray(leaves[p.index]).reshape(p.m, p.n)
                for p in plans]
        errs = []
        for p, mat in zip(plans, mats):
            e = self._errors.get(p.index)
            if e is None or e.shape != (p.m, p.n):
                e = jnp.zeros((p.m, p.n), jnp.float32)
            errs.append(e)
        qs = [jnp.asarray(self._q_for(p, epoch)) for p in plans]
        mats_e, ps = _dev_phase1(mats, errs, qs)
        for p, me in zip(plans, mats_e):
            self._mat_cache[p.index] = me
        if self.keep_factors_on_device:
            return list(ps)  # the device wire codec consumes them as-is
        # collective-safe host pull: on multi-host slices the factor
        # outputs inherit the gradients' cross-process sharding
        from dalle_tpu.parallel.multihost import host_global
        return host_global(ps)

    def phase2_qs(self, plans: List[_TensorPlan],
                  averaged_ps: List[np.ndarray]) -> List[np.ndarray]:
        """Orthogonalize averaged Ps, compute the Q factors to average."""
        self._p_orth = {}
        mats_e = [self._mat_cache[p.index] for p in plans]
        host_ps = [np.asarray(pa, np.float32).reshape(p.m, self.rank)
                   for p, pa in zip(plans, averaged_ps)]
        if self.host_orthogonalize:
            # MGS on the wire's host bytes directly — one upload of the
            # orthonormal basis, no device round-trip
            p_orths = [jnp.asarray(orthogonalize(pa)) for pa in host_ps]
            qs = _dev_phase2_preorth(mats_e, p_orths)
        else:
            p_orths, qs = _dev_phase2(mats_e,
                                      [jnp.asarray(pa) for pa in host_ps])
        for p, po in zip(plans, p_orths):
            self._p_orth[p.index] = po
        if self.keep_factors_on_device:
            return list(qs)
        from dalle_tpu.parallel.multihost import host_global
        return host_global(qs)

    def reconstruct(self, leaves: List[Any],
                    plans: List[_TensorPlan],
                    averaged_qs: List[np.ndarray]) -> List[Any]:
        """Replace planned leaves with the rank-r group average and update
        the (device-resident) error feedback. Planned outputs are device
        arrays — in the single-process trainer they flow straight into the
        jitted optimizer apply with no host round-trip."""
        out = list(leaves)
        mats_e = [self._mat_cache[p.index] for p in plans]
        p_orths = [self._p_orth[p.index] for p in plans]
        q_avgs = [jnp.asarray(np.asarray(qa, np.float32).reshape(
            p.n, self.rank)) for p, qa in zip(plans, averaged_qs)]
        approx, errs = _dev_reconstruct(mats_e, p_orths, q_avgs)
        for p, ap, e in zip(plans, approx, errs):
            self._errors[p.index] = e
            out[p.index] = ap.reshape(p.shape)
            self._mat_cache.pop(p.index, None)
        self._p_orth = {}
        return out

    def abandon_round(self) -> None:
        """Discard this round's in-flight state after an incomplete factor
        exchange: the caller keeps its local gradients, so error feedback
        for the round must not be recorded (the local grads ARE exact) and
        cached matrices are dropped."""
        self._mat_cache.clear()
        self._p_orth = {}


def average_with_powersgd(
        compressor: PowerSGDCompressor,
        leaves: Sequence[Any],
        reduce_fn,
        epoch: int = 0,
) -> List[Any]:
    """Run the full PowerSGD exchange.

    ``leaves`` may be jax device arrays (the trainer's accumulated grads —
    no host pull happens for the planned tensors) or numpy arrays.
    ``reduce_fn(tensors: List[np.ndarray], phase: str) -> List[np.ndarray]``
    performs the group averaging for one phase ("p" or "q") — in
    production the butterfly all-reduce (swarm/allreduce.py) with the phase
    folded into the tag prefix, in tests a plain mean across peers. It may
    raise :class:`IncompleteRound` to signal that this peer's averaged
    bytes may diverge from other survivors' (a member died mid-round);
    the caller then keeps its exact local gradients for the epoch.

    Small/1D tensors that the plan skips are averaged exactly in their own
    round, so the result is: rank-r approximate mean for big matrices
    (returned as device arrays), exact mean for everything else (returned
    as the numpy arrays the wire produced).
    """
    leaves = list(leaves)
    plans = compressor.plan(leaves)
    planned = {p.index for p in plans}

    try:
        ps = compressor.phase1_ps(leaves, plans, epoch)
        averaged_ps = reduce_fn(ps, "p") if ps else []
        qs = compressor.phase2_qs(plans, averaged_ps)
        unplanned = [leaves[i] for i in range(len(leaves))
                     if i not in planned]
        if compressor.keep_factors_on_device:
            # the raw tail rides the wire from wherever it lives — the
            # device codec flattens/pushes as needed, no eager pull
            raw = unplanned
        else:
            from dalle_tpu.parallel.multihost import host_global
            raw = [a.astype(np.float32, copy=False)
                   for a in host_global(unplanned)]
        averaged_tail = reduce_fn(qs + raw, "q") if (qs or raw) else []
    except IncompleteRound:
        compressor.abandon_round()
        return [jnp.asarray(x, jnp.float32) if not isinstance(x, np.ndarray)
                else np.array(x, np.float32) for x in leaves]
    averaged_qs = averaged_tail[:len(qs)]
    averaged_raw = averaged_tail[len(qs):]

    out = compressor.reconstruct(leaves, plans, averaged_qs)
    it = iter(averaged_raw)
    for i in range(len(out)):
        if i not in planned:
            # np.shape avoids materializing device leaves just for
            # their geometry
            out[i] = np.asarray(next(it)).reshape(np.shape(leaves[i]))
    return out
