"""The collaborative optimizer: swarm-synchronous training facade.

Capability parity with ``hivemind.Optimizer`` as configured by the
reference (task.py:122-135): peers accumulate gradients locally until the
swarm collectively reaches ``target_batch_size``; then they form a group
(matchmaking), average gradients with a compressed butterfly all-reduce,
and every peer applies an identical optimizer update — so the swarm
behaves like one giant synchronous data-parallel trainer with elastic
membership. Surfaces mirrored from the reference's call sites:
``.step()`` (run_trainer_tpu.py:88), ``.local_epoch`` (callback.py:60),
``.tracker`` (callback.py:63,79), ``.load_state_from_peers()``
(callback.py:41), ``on_after_global_step`` / ``on_load_state_from_peers``
callbacks (run_trainer_tpu.py:66-67).

TPU-native seam: gradients arrive as a JAX pytree from a jitted
``make_grad_step`` (device math stays in XLA); accumulation is a jitted
tree-add on device; buffers cross to the host exactly once per swarm
epoch for the wire all-reduce; the averaged result feeds the jitted
``make_apply_step`` (LAMB on device — the reference's CPU offload was a
2021-GPU workaround, SURVEY §2 parallelism table). The optimizer update
is identical on every peer, so parameters stay bit-synchronized without
per-epoch state averaging; periodic state averaging
(``average_state_every``) bounds drift from lossy wire compression, and
``load_state_from_peers`` handles joiners and stragglers.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from dalle_tpu.config import CollabConfig
from dalle_tpu.obs.trace import span as obs_span
from dalle_tpu.swarm import compression
from dalle_tpu.swarm.allreduce import run_allreduce
from dalle_tpu.swarm.dht import DHT
from dalle_tpu.swarm.matchmaking import make_group
from dalle_tpu.swarm.progress import ProgressTracker
from dalle_tpu.swarm.state_transfer import (StateServer,
                                            load_state_from_peers)

logger = logging.getLogger(__name__)

_CODECS = {"none": compression.NONE, "float16": compression.FLOAT16,
           "uniform8bit": compression.UNIFORM8BIT, "size_adaptive": None}


class _PendingRound:
    """An overlapped swarm round in flight on a background thread.

    Holds the gradient accumulator handed off at launch (``leaves``, still
    on device) and receives the wire outcome (``result`` = averaged host
    arrays, or None for an ALONE epoch whose device grads flow straight to
    the apply). The worker thread only touches the wire + host pulls; all
    train-state mutation happens at reconcile time on the training thread.
    """

    def __init__(self, epoch: int, treedef, leaves: List[Any],
                 weight: float, weight_int: int):
        self.epoch = epoch
        self.treedef = treedef
        self.leaves = leaves
        self.weight = weight
        self.weight_int = weight_int          # frozen progress report value
        self.result: Optional[List[np.ndarray]] = None
        self.error: Optional[BaseException] = None
        self.group_size = 1
        self.timings: dict = {}
        self.overlapped_steps = 0             # grad steps run during round
        self.hidden_s = 0.0                   # round wall hidden from chip
        self.done = threading.Event()
        self.thread: Optional[threading.Thread] = None
        # hop-granular progress (pipeline_hops): run_allreduce's
        # progress hook bumps these from codec/drain threads while the
        # training thread polls hop_progress() between grad steps —
        # the in-flight round stops presenting as one opaque wall
        self._hop_lock = threading.Lock()
        self.hops = {"scatter": 0, "reduce": 0, "gather": 0}

    def note_hop(self, leg: str, part: int) -> None:
        """run_allreduce ``progress`` sink — called from pool/drain
        threads on part-granular completion events; thread-safe."""
        with self._hop_lock:
            if leg in self.hops:
                self.hops[leg] += 1

    def hop_progress(self) -> dict:
        with self._hop_lock:
            return dict(self.hops)


class _FollowerEMA:
    samples_per_second = 0.0

    def reset_timer(self) -> None:
        pass


class _FollowerTracker:
    """Tracker stand-in for non-coordinator processes of a multi-host
    slice: the loop's bookkeeping surface with no wire behind it (the
    coordinator's tracker is authoritative for the whole slice)."""

    min_refresh_period = 0.0

    def __init__(self) -> None:
        self.performance_ema = _FollowerEMA()

    def report_local_progress(self, *a, **k) -> None:
        pass

    def reset_epoch(self, *a, **k) -> None:
        pass


def accumulate_grads(acc, grads, scale):
    """``acc + grads * scale`` in f32, leaf by leaf: the program of
    ``collab/accumulate`` (named, so that the compile counter and the
    memory account can say ``accumulate_grads``)."""
    return jax.tree.map(lambda a, g: a + g.astype(jnp.float32) * scale,
                        acc, grads)


class CollaborativeOptimizer:
    """Owns the train state and drives swarm-synchronous updates.

    Args:
      dht: this peer's swarm node.
      cfg: swarm-wide semantics (target batch, timeouts, compression).
      state: initial TrainState (params + opt state + step).
      apply_step: jitted ``(state, grads) -> state`` (make_apply_step).
      client_mode: outbound-only peer — contributes gradients but owns no
        all-reduce part (reference arguments.py:89-92).
      serve_state: run a StateServer thread so joiners can bootstrap from
        this peer (reference callback.py:41 semantics).
    """

    def __init__(self, dht: Optional[DHT], cfg: CollabConfig, state: Any,
                 apply_step: Callable[[Any, Any], Any],
                 client_mode: bool = False,
                 serve_state: bool = True,
                 matchmaking_min_group: int = 2,
                 authorizer=None,
                 role=None,
                 tracer=None,
                 memory=None):
        from dalle_tpu.parallel.multihost import SliceRole
        self.role = role or SliceRole()
        if self.role.swarm_enabled and dht is None:
            raise ValueError("the slice coordinator needs a DHT")
        self.dht = dht
        self.cfg = cfg
        self.state = state
        self.apply_step = apply_step
        self.client_mode = client_mode
        self.matchmaking_min_group = matchmaking_min_group
        # Optional access-token authorizer (swarm/auth.py): gates group
        # membership the way the reference's HF authorizer gates the swarm
        # (huggingface_auth.py:46-193, wired at task.py:95-99).
        self.authorizer = authorizer
        # Flight recorder (dalle_tpu/obs, OBSERVABILITY.md): the round
        # lifecycle's existing timing seams become spans whose trace id
        # is the PROTOCOL round id ({run_id}:grads:{epoch}), so several
        # peers' JSONL files merge into one cross-peer round timeline
        # with no clock sync. What runs every step() is spans of plane
        # "train" on the same recorder. A trainer passes its always-on
        # ring (TrainingTask); a library caller's None (the default)
        # records nothing and every round path stays byte-identical —
        # each seam pays one `is None` test (transparency pinned by
        # tests/test_obs.py).
        self.tracer = tracer
        # The trainer's memory account (obs/memory.py): told after every
        # accumulate, which is where a step holds the most of the device
        self.memory = memory
        if tracer is None and cfg.trace_file:
            from dalle_tpu.obs.trace import Tracer
            self.tracer = Tracer(
                peer=(dht.peer_id[:12] if dht is not None else "local"),
                sink_path=cfg.trace_file,
                ring_bytes=cfg.trace_ring_kb * 1024)
        self.local_epoch = 0
        self.local_samples = 0
        # Multi-host slices (parallel/multihost.py): exactly one process —
        # the coordinator — speaks the swarm protocol; followers run the
        # same jitted steps (their devices already join the global-mesh
        # collectives) and receive decisions/averages via broadcasts.
        # Peer-health ledger (swarm/health.py): allreduce bans feed
        # strikes; matchmaking and progress aggregation down-rank repeat
        # offenders until the strikes decay. Local knowledge only.
        # Byzantine defense wiring (CHAOS.md "Defense in depth"): a
        # swarm-speaking peer always arms the whole trust plane —
        # content screening + the frame-weight clamp ride every
        # allreduce call below; the gossip worker publishes/folds
        # signed strike receipts until shutdown() reaps it.
        self._gossip = None
        if self.role.swarm_enabled:
            from dalle_tpu.swarm.audit import (AuditPolicy, AuditWorker,
                                               EvidencePlane)
            from dalle_tpu.swarm.health import PeerHealthLedger, StrikeGossip
            from dalle_tpu.swarm.screening import GradientScreen, ScreenPolicy
            self.ledger = PeerHealthLedger()
            self.tracker = ProgressTracker(
                dht, cfg.run_id, cfg.target_batch_size,
                client_mode=client_mode, ledger=self.ledger,
                max_epoch_lead=cfg.progress_max_epoch_lead)
            self._screen = GradientScreen(ScreenPolicy(
                min_senders=cfg.screen_min_senders,
                max_drop_frac=cfg.screen_max_drop_frac,
                norm_tolerance=cfg.screen_norm_tolerance,
                cosine_floor=cfg.screen_cosine_floor,
                abs_norm_ceiling=cfg.screen_abs_norm_ceiling))
            mpw = cfg.max_peer_weight
            if mpw is None:
                mpw = float(cfg.target_batch_size)
            self._max_peer_weight = mpw if mpw > 0 else None
            self._gossip = StrikeGossip(
                dht, self.ledger, cfg.run_id,
                period=cfg.strike_gossip_period)
            self._gossip.start()
            # Verified aggregation (swarm/audit.py): the worker drains
            # completed rounds' RoundAudit retention off the training
            # thread — fetches challenged owners' transcripts, replays
            # the averages, bit-compares, and strikes (a replay
            # mismatch gossips through the receipt plane above, with
            # the proof evidence attached). The retained-round ring is
            # byte-bounded (cfg.audit_ring_bytes). Round repair
            # (swarm/repair.py): replayed-bytes-mismatch convictions
            # queue their honest-minus-served correction on the repair
            # plane; _apply_averaged drains it into the next gradient
            # application. Reaped by shutdown() before the DHT goes
            # down.
            # audit plane wiring: created here before the round worker
            # exists; shutdown() clears them only AFTER auditor.stop()
            # joins (the dht ordering contract) — the in-between reads
            # from the worker see either None or a live worker
            # graftlint: handoff=init-then-joined-teardown
            self._auditor = None
            # graftlint: handoff=init-then-joined-teardown
            self._audit_policy = AuditPolicy(
                frac=cfg.audit_frac, ttl=cfg.audit_ttl)
            self._repair = None
            if jax.process_count() == 1:
                # single-process peers only: a multi-host slice
                # would need every correction broadcast to stay in
                # lockstep (followers run no auditor to agree
                # with), and a plane nothing drains would just
                # retain part-sized copies — don't create one.
                # Factor and state convictions queue corrections
                # too, drained at their own phase's application site
                # (prefix-scoped — a factor correction never lands in
                # a gradient vector)
                from dalle_tpu.swarm.repair import RepairPlane
                self._repair = RepairPlane(accept_prefix=(
                    f"{cfg.run_id}_grads", f"{cfg.run_id}_grads_p",
                    f"{cfg.run_id}_grads_q", f"{cfg.run_id}_state"))
            # Evidence-by-reference plane: bundles past
            # PROOF_MAX_BYTES ride the receipt as digest +
            # mailbox reference; this plane serves ours and
            # fetches theirs (budgeted, hash-checked,
            # failover-capable).
            self._evidence = EvidencePlane(
                dht, cfg.run_id,
                max_bytes=cfg.proof_fetch_max_bytes,
                budget_s=cfg.proof_fetch_budget_s,
                retries=cfg.proof_fetch_retries,
                tracer=self.tracer)
            # bind-once wiring before the gossip worker's first
            # over-budget publish can look at it
            self._gossip.evidence_store = self._evidence
            self._auditor = AuditWorker(
                dht, self.ledger, repair=self._repair,
                max_bytes=cfg.audit_ring_bytes,
                # with the by-reference plane armed, evidence has no
                # inline size cap — oversized bundles publish by
                # reference instead of degrading to capped accusation
                evidence_limit=0)
            self._auditor.start()
        else:
            self.ledger = None
            self.tracker = _FollowerTracker()
            self._screen = None
            self._max_peer_weight = None
            self._auditor = None
            self._audit_policy = None
            self._repair = None
            self._evidence = None
        self.on_after_global_step: List[Callable[[], None]] = []
        self.on_load_state_from_peers: List[Callable[[], None]] = []
        # Wire-codec execution backend (swarm/device_codec.py): "device"
        # quantizes/dequantizes on the accelerator and keeps gradient
        # leaves on device until the codec consumes them; the wire bytes
        # are identical either way. Resolved once — the backend is a
        # property of this process's hardware, not of the round.
        from dalle_tpu.swarm.device_codec import resolve_backend
        self._codec_backend = resolve_backend(cfg.wire_codec_backend)
        # device-array handoff is only valid when every leaf lives whole
        # on this process (multi-process slices pull via the collective
        # host_global path regardless of codec backend)
        self._device_grad_handoff = (
            self._codec_backend == compression.DEVICE_BACKEND
            and jax.process_count() == 1)
        if cfg.grad_compression == "power_sgd":
            # rank-r low-rank factor exchange (swarm/powersgd.py); the
            # factors themselves ride the wire as fp16
            from dalle_tpu.swarm.powersgd import PowerSGDCompressor
            self._powersgd = PowerSGDCompressor(
                cfg.powersgd_rank,
                host_orthogonalize=cfg.powersgd_host_orthogonalize,
                keep_factors_on_device=self._device_grad_handoff)
            self._grad_codec = compression.FLOAT16
        else:
            self._powersgd = None
            self._grad_codec = _CODECS[cfg.grad_compression]
        self._state_codec = _CODECS[cfg.state_compression]
        # In-collective quantization (r15): wire_bits_reduce/_gather pin
        # the butterfly legs' codecs for the run (receivers reject codec
        # flapping); ef_residuals arms both error-feedback legs —
        # sender-side scatter compensation and the owner's gather
        # second stage (swarm/error_feedback.py). Grad rounds only:
        # state averaging keeps its own codec, PowerSGD factor rounds
        # are a different compression family entirely.
        wb_r = cfg.wire_bits_reduce
        wb_g = cfg.wire_bits_gather
        ef_on = cfg.ef_residuals
        # the shared knob mapping (compression.codec_for_bits) raises
        # on anything outside {None, 4, 8}
        reduce_codec = compression.codec_for_bits(wb_r)
        gather_codec = compression.codec_for_bits(wb_g)
        if (wb_r is not None or wb_g is not None or ef_on) \
                and self._powersgd is not None:
            raise ValueError(
                "wire_bits_*/ef_residuals pin the uniform wire codec; "
                "power_sgd exchanges low-rank factors — choose one "
                "compression family")
        if ef_on and (wb_r is None or wb_g is None):
            raise ValueError(
                "ef_residuals carries quantization error between rounds, "
                "which is only meaningful against a STABLE codec: pin "
                "both wire_bits_reduce and wire_bits_gather (8 or 4)")
        if reduce_codec is not None:
            self._grad_codec = reduce_codec
        self._gather_codec = gather_codec
        # a wire_bits run is a PINNED run: receivers reject codec
        # flapping (run_allreduce pin_codec)
        self._pin_codec = wb_r is not None or wb_g is not None
        # Per-part pipelined butterfly (r19): OFF keeps every wire round
        # byte-identical; ON moves wall-clock only (allreduce.py's
        # pipeline_hops contract). Grad rounds only — PowerSGD factor
        # rounds and state averaging keep the sequential protocol (they
        # are latency-insensitive and run rarely).
        self._pipeline_hops = bool(cfg.pipeline_hops)
        self._pipeline_depth = int(cfg.pipeline_depth)
        if ef_on:
            from dalle_tpu.swarm.error_feedback import ErrorFeedback
            self._ef_scatter = ErrorFeedback()
            self._ef_gather = ErrorFeedback()
        else:
            self._ef_scatter = None
            self._ef_gather = None
        # Proof-carrying receipts (swarm/audit.ProofVerifier): with the
        # verifier armed, a gossiped owner-audit-fail receipt carrying
        # evidence is re-verified by REPLAYING it under THIS peer's
        # round config — verified proofs convict with no local
        # corroboration (health.proven_strike), unverifiable ones are
        # dropped without ledger effect. Attached after codec
        # resolution: the verifier judges by the same codec/pin/screen/
        # clamp this peer's own rounds run under (the run-config-
        # homogeneity contract the r14 audit already documents).
        if self.role.swarm_enabled:
            from dalle_tpu.swarm.allreduce import CHUNK_ELEMS
            from dalle_tpu.swarm.audit import ProofVerifier
            self._gossip.verifier = ProofVerifier(
                cfg.run_id, frac=self._audit_policy.frac,
                chunk_elems=CHUNK_ELEMS, codec=self._grad_codec,
                adaptive_threshold=cfg.size_adaptive_threshold,
                screen=self._screen,
                max_peer_weight=self._max_peer_weight,
                gather_codec=self._gather_codec,
                pinned=self._grad_codec if self._pin_codec else None,
                phase_overrides={
                    # the aux phases run their own codec config — a
                    # proof from them must be judged under it
                    "powersgd": {"gather_codec": None, "pinned": None},
                    "state": {"codec": self._state_codec,
                              "gather_codec": None, "pinned": None},
                },
                # receipts whose evidence rides by reference are
                # resolved through the fetch plane before replay
                fetcher=self._evidence)
        self._grad_acc = None
        # the accumulator is donated: the sum is written over it. Undonated,
        # every step held the old accumulator, the step's gradients and the
        # new accumulator at once, a third f32 tree of 4 bytes a parameter
        # until the program had run (PERF.md section 6, PR 41: 1.88 GiB on
        # trinitymini). Whoever takes the accumulator's leaves (a launched
        # round, a global step) sets _grad_acc to None or is done with
        # them before the next accumulate.
        self._accumulate = jax.jit(accumulate_grads, donate_argnums=0)
        self._pending: Optional[_PendingRound] = None
        self._next_resync = 0.0
        self.last_timings: dict = {}
        self._apply_timings: dict = {}
        self._server: Optional[StateServer] = None
        if serve_state and not client_mode and self.role.swarm_enabled:
            from dalle_tpu.parallel.multihost import is_fully_addressable
            leaves = jax.tree_util.tree_leaves((state.params,
                                                state.opt_state))
            if all(is_fully_addressable(x) for x in leaves):
                self._server = StateServer(
                    dht, cfg.run_id, self._state_snapshot,
                    codec=self._state_codec,
                    adaptive_threshold=cfg.size_adaptive_threshold,
                    epoch_fn=lambda: self.local_epoch,
                    stream_timeout=cfg.averaging_timeout,
                    tracer=self.tracer).start()
            else:
                # the snapshot runs on a server thread that cannot join
                # the cross-process all-gather a sharded state needs;
                # such slices train fine but don't serve joiners
                logger.warning(
                    "state is sharded across processes: state server "
                    "disabled on this slice (joiners must bootstrap from "
                    "an unsharded peer or a checkpoint)")
        self.tracker.report_local_progress(0, 0, force=True)

    # -- state (de)construction -----------------------------------------

    def _state_leaves(self) -> List[np.ndarray]:
        """Global host copies of the state leaves. COLLECTIVE when the
        state is sharded across processes — callers are the lockstep,
        broadcast-synchronized paths (startup sync, NaN rollback,
        load_state_from_peers)."""
        from dalle_tpu.parallel.multihost import host_global
        leaves = jax.tree_util.tree_leaves(
            (self.state.params, self.state.opt_state))
        return host_global(leaves)

    def _state_snapshot(self):
        """StateServer snapshot — runs on a background thread, so it must
        NOT join collectives; the server is only started when the state is
        fully addressable (see __init__)."""
        leaves = jax.tree_util.tree_leaves(
            (self.state.params, self.state.opt_state))
        return self.local_epoch, [np.asarray(x) for x in leaves]

    def _replace_state_leaves(self, arrays: List[np.ndarray]) -> None:
        from dalle_tpu.swarm.state_transfer import apply_state_arrays
        self.state = apply_state_arrays(self.state, arrays)

    # -- the hot path ----------------------------------------------------

    # step() decision codes, broadcast coordinator -> followers in
    # multi-host slices (parallel/multihost.py)
    _CONTINUE, _GLOBAL_STEP, _RESYNC = 0, 1, 2

    def step(self, grads: Any, batch_size: int) -> bool:
        """Record one local accumulation step; run a global step when the
        swarm is ready. Returns True iff a global step (the optimizer
        apply) happened during this call.

        With ``cfg.delay_optimizer_step`` (the reference's default,
        task.py:129-131) the swarm round — matchmaking + all-reduce — runs
        on a background thread while step() keeps accumulating gradients
        for the NEXT epoch into a fresh buffer, so the chip never idles
        through the 15 s matchmaking + up-to-60 s all-reduce window. The
        epoch counter and the tracker's published progress stay frozen at
        the launch values until the round's result is applied (reconciled)
        at a later step() boundary — to every other peer the DHT looks
        identical to a synchronous round in progress, so stragglers still
        join the in-flight round instead of resyncing. Samples accumulated
        during the round were computed against the pre-apply params and
        count toward the next epoch: the one-step staleness
        delay_optimizer_step trades for zero device idle.

        In a multi-host slice every process calls step() in lockstep (the
        jitted grad step is itself a global collective); the coordinator's
        decision is broadcast so followers run the identical control flow.
        Overlap is disabled there: followers cannot join broadcasts from a
        background thread, so slices run the synchronous path.
        """
        with obs_span(self.tracer, "train", "collab/step"):
            return self._step(grads, batch_size)

    def _step(self, grads: Any, batch_size: int) -> bool:
        """:meth:`step` proper. Its parts are spans of plane ``train``
        (OBSERVABILITY.md), children of ``collab/step`` and, through it,
        of the loop's step: recorded at dispatch, never by waiting for
        the device, so the recorder does not change what it times."""
        from dalle_tpu.parallel.multihost import broadcast_decision
        tracer = self.tracer

        did_global = False
        if self._pending is not None and self._pending.done.is_set():
            with obs_span(tracer, "train", "collab/reconcile"):
                self._finish_pending()
            did_global = True

        # while a round is in flight the span names it, so the merged
        # timeline shows the accumulates between that round's hop spans
        in_round = ({"round": self._round_trace(self._pending.epoch)}
                    if tracer is not None and self._pending is not None
                    else {})
        with obs_span(tracer, "train", "collab/accumulate", **in_round):
            if self._grad_acc is None:
                # placed like the gradients: the accumulate then sees the
                # operands of every later call and compiles once (the
                # compile counter found a second compile at step 2)
                self._grad_acc = jax.tree.map(
                    lambda g: jnp.zeros(g.shape, jnp.float32,
                                        device=g.sharding), grads)
            self._grad_acc = self._accumulate(
                self._grad_acc, grads, float(batch_size))
        if self.memory is not None:
            self.memory.after_accumulate(self._grad_acc)
        self.local_samples += int(batch_size)
        with obs_span(tracer, "train", "collab/progress"):
            if self._pending is not None:
                # round in flight: report the FROZEN pre-round progress
                # (pure liveness — publishing the restarted counter would
                # deflate the swarm's sample total and flip
                # ready_to_update off for peers still deciding to join);
                # decisions wait for the reconcile
                self._pending.overlapped_steps += 1
                self.tracker.report_local_progress(
                    self.local_epoch, self._pending.weight_int)
                return did_global
            # after a reconcile the tracker just force-published the
            # epoch reset (samples=0) milliseconds ago: an unforced
            # report here would be THROTTLED, the swarm would see 0
            # samples, and this call's ready check would miss — costing a
            # whole grad step of epoch latency every round (measured:
            # 44 s epochs vs 22 s)
            self.tracker.report_local_progress(
                self.local_epoch, self.local_samples, force=did_global)

        decision = self._CONTINUE
        min_epoch = 0
        with obs_span(tracer, "train", "collab/decide"):
            if self.role.swarm_enabled:
                progress = self.tracker.global_progress()
                if progress.epoch > self.local_epoch:
                    # keep accumulating between throttled attempts:
                    # hammering load_state_from_peers starves the host
                    # (and the swarm's state servers) without helping us
                    # catch up any faster
                    if time.monotonic() >= self._next_resync:
                        decision = self._RESYNC
                        min_epoch = progress.epoch
                        self._next_resync = time.monotonic() + 1.0
                elif progress.ready_to_update:
                    decision = self._GLOBAL_STEP
            decision = broadcast_decision(decision)

        if decision == self._RESYNC:
            if self.role.swarm_enabled:
                logger.info(
                    "behind the swarm (local %d < global %d): resyncing",
                    self.local_epoch, min_epoch)
            with obs_span(tracer, "train", "collab/resync"):
                self.load_state_from_peers(min_epoch=min_epoch)
            return did_global
        if decision == self._GLOBAL_STEP:
            if self._delay_rounds:
                with obs_span(tracer, "train", "collab/launch_round"):
                    self._launch_round()
                return did_global  # the apply lands at a later reconcile
            with obs_span(tracer, "train", "collab/global_step"):
                self._run_global_step()
            return True
        return did_global

    # -- overlapped rounds (delay_optimizer_step) -------------------------

    @property
    def _delay_rounds(self) -> bool:
        """Overlapped rounds run only where the wire thread can act alone:
        single-process peers that speak the swarm protocol. Multi-host
        slices keep the synchronous path (followers must join broadcasts
        in lockstep with the coordinator's training thread)."""
        from dalle_tpu.parallel.multihost import process_count
        return (self.cfg.delay_optimizer_step and self.role.swarm_enabled
                and process_count() == 1)

    def _new_round_audit(self, epoch: int, phase_suffix: str = "grads"):
        """A fresh per-round audit container, or None on a peer that
        runs no auditor (a follower of a multi-host slice).
        ``phase_suffix`` names the averaging phase's prefix leg: the
        main gradient rounds ("grads"), the PowerSGD factor rounds
        ("grads_p"/"grads_q") and the periodic state averaging
        ("state") each ride the same butterfly and the same
        challenge/transcript/replay machinery under their own
        prefix."""
        if self._auditor is None:
            return None
        from dalle_tpu.swarm.audit import RoundAudit
        return RoundAudit(f"{self.cfg.run_id}_{phase_suffix}", epoch,
                          self._audit_policy)

    def _round_trace(self, epoch: int) -> str:
        """The PROTOCOL round id (shared by every member of the round)
        — the cross-peer correlation key for this epoch's spans."""
        return f"{self.cfg.run_id}:grads:{epoch}"

    def _trace_allreduce(self, trace: str, t_start: float, t_end: float,
                         rep: Optional[dict], group_size: int) -> None:
        """Convert a completed exchange's measured walls into spans —
        the allreduce envelope plus the wire report's per-protocol-phase
        walls (``report["phases"]``), re-timing nothing. Sub-phase start
        times are chained estimates (the report records durations in
        protocol order); the durations are the measurements."""
        tr = self.tracer
        if tr is None:
            return
        attrs = {"group": group_size}
        if rep is not None and "complete" in rep:
            attrs["complete"] = bool(rep["complete"])
        tr.add("swarm", "allreduce", trace, t_start, t_end - t_start,
               **attrs)
        t = t_start
        for name, dur in ((rep or {}).get("phases") or {}).items():
            if not isinstance(dur, (int, float)):
                # the per-hop rows ride the same dict under "hops";
                # their live spans were already emitted in-round
                continue
            phase = "ar_" + (name[:-2] if name.endswith("_s") else name)
            tr.add("swarm", phase, trace, t, dur)
            t += dur

    def _launch_round(self) -> None:
        """Hand the gradient accumulator to a background wire thread and
        start a fresh buffer; the epoch advances when the round's result
        is applied (``_finish_pending``)."""
        pending = _PendingRound(
            epoch=self.local_epoch,
            treedef=jax.tree_util.tree_structure(self._grad_acc),
            leaves=jax.tree_util.tree_leaves(self._grad_acc),
            weight=float(max(self.local_samples, 1)),
            weight_int=self.local_samples)
        self._grad_acc = None
        self.local_samples = 0
        pending.thread = threading.Thread(
            target=self._round_worker, args=(pending,),
            name="swarm-round", daemon=True)
        self._pending = pending
        pending.thread.start()

    def _round_worker(self, pending: _PendingRound) -> None:
        """Wire half of an overlapped round: matchmaking + all-reduce.
        Touches the DHT and host copies of the handed-off gradients only —
        never ``self.state`` (the training thread owns it)."""
        t0 = time.perf_counter()
        try:
            group = make_group(
                self.dht, f"{self.cfg.run_id}_grads", pending.epoch,
                weight=pending.weight,
                matchmaking_time=self.cfg.matchmaking_time,
                min_group_size=self.matchmaking_min_group,
                client_mode=self.client_mode, authorizer=self.authorizer,
                encrypt=self.cfg.encrypt_data_plane, ledger=self.ledger)
            t_match = time.perf_counter()
            pending.timings["matchmaking_s"] = round(t_match - t0, 4)
            if self.tracer is not None:
                self.tracer.add(
                    "swarm", "matchmaking", self._round_trace(
                        pending.epoch), t0, t_match - t0,
                    group=group.size if group is not None else 1)
            if group is not None and group.size > 1:
                budget = min(self.cfg.allreduce_timeout,
                             max(1.0, self.cfg.averaging_timeout
                                 - (t_match - t0)))
                if self._powersgd is not None:
                    grads_local = [g / pending.weight
                                   for g in pending.leaves]
                    from dalle_tpu.swarm.powersgd import \
                        average_with_powersgd
                    averaged = average_with_powersgd(
                        self._powersgd, grads_local,
                        self._powersgd_reduce_fn(group, pending.weight,
                                                 budget, sharded=False),
                        epoch=pending.epoch)
                else:
                    t_pull = time.perf_counter()
                    if self._device_grad_handoff:
                        # hand device arrays to the codec: the divide,
                        # flatten and quantize all run on device; the
                        # round's one bulk host copy (reduce accumulate
                        # + gather template) lands in allreduce's
                        # flatten phase instead of per-leaf pulls here
                        grads_local = [g / pending.weight
                                       for g in pending.leaves]
                    else:
                        grads_local = [np.asarray(g) / pending.weight
                                       for g in pending.leaves]
                    pending.timings["grad_pull_s"] = round(
                        time.perf_counter() - t_pull, 4)
                    ra = self._new_round_audit(pending.epoch)
                    # the report dict is write-only wire telemetry;
                    # requested only when the tracer consumes it so the
                    # recorder-off call is literally the historic one
                    rep = {} if self.tracer is not None else None
                    averaged = run_allreduce(
                        self.dht, group, f"{self.cfg.run_id}_grads",
                        pending.epoch, grads_local, weight=pending.weight,
                        allreduce_timeout=budget, codec=self._grad_codec,
                        adaptive_threshold=self.cfg.size_adaptive_threshold,
                        codec_backend=self._codec_backend,
                        ledger=self.ledger, screen=self._screen,
                        max_peer_weight=self._max_peer_weight,
                        audit=ra, gather_codec=self._gather_codec,
                        ef_scatter=self._ef_scatter,
                        ef_gather=self._ef_gather,
                        pin_codec=self._pin_codec, report=rep,
                        pipeline_hops=self._pipeline_hops,
                        pipeline_depth=self._pipeline_depth,
                        tracer=self.tracer,
                        trace=self._round_trace(pending.epoch),
                        progress=pending.note_hop)
                    if ra is not None:
                        self._auditor.submit(ra)
                    self._trace_allreduce(
                        self._round_trace(pending.epoch), t_match,
                        time.perf_counter(), rep, group.size)
                pending.result = averaged
                pending.timings["allreduce_s"] = round(
                    time.perf_counter() - t_match, 4)
            if group is not None:
                pending.group_size = group.size
        # not silent, deferred: the error crosses threads on the round
        # object and _finish_pending logs it (with the epoch) on the
        # training thread, where the apply-local-grads fallback runs
        # graftlint: disable=silent-except
        except BaseException as e:  # noqa: BLE001 - reported at reconcile
            pending.error = e
        finally:
            pending.hidden_s = time.perf_counter() - t0
            pending.done.set()

    def _finish_pending(self, block: bool = False,
                        discard: bool = False) -> None:
        """Reconcile an overlapped round on the training thread: apply its
        averaged gradients (or, for an ALONE / failed round, the handed-off
        device gradients — the synchronous path's exact fallback) and
        advance the epoch. ``block`` waits for the wire thread (bounded by
        the round's own matchmaking/averaging deadlines); ``discard``
        drops the round instead of applying (resync/teardown paths)."""
        pending = self._pending
        if pending is None:
            return
        if not pending.done.is_set():
            if not block:
                return
            pending.thread.join()
        else:
            pending.thread.join()
        self._pending = None
        if discard:
            return
        if pending.error is not None:
            logger.warning(
                "overlapped round for epoch %d failed (%r): applying "
                "local gradients", pending.epoch, pending.error)
        averaged = pending.result
        if averaged is None:
            # ALONE epoch (or wire failure): the accumulated grads never
            # left the device — they flow straight into the jitted apply
            averaged = [g / pending.weight for g in pending.leaves]
        self._apply_averaged(pending.treedef, averaged,
                             preserve_accumulator=True)
        # keep the per-phase schema identical to the synchronous path
        # (metrics consumers key on these fields)
        pending.timings.setdefault("grad_pull_s", 0.0)
        pending.timings.setdefault("allreduce_s", 0.0)
        self.last_timings = {
            **pending.timings, **self._apply_timings,
            "group_size": pending.group_size,
            "overlapped_steps": pending.overlapped_steps,
            "hidden_s": round(pending.hidden_s, 4),
            "round_hops": pending.hop_progress(),
            "robust": self.robustness_snapshot(),
        }
        logger.info(
            "overlapped global step -> epoch %d (group=%d, %d grad steps "
            "ran during the %.2fs round, %s)", self.local_epoch,
            pending.group_size, pending.overlapped_steps, pending.hidden_s,
            self.last_timings)

    def round_progress(self) -> Optional[dict]:
        """Hop-granular progress of the in-flight overlapped round, or
        None when no round is pending: part-completion counts per leg
        ({"scatter", "reduce", "gather"}) plus the epoch and the grad
        steps overlapped so far — the training loop's window into a
        round that no longer presents as one opaque wall. Counts only
        advance on pipelined rounds' scatter leg (the sequential burst
        submit has no per-part completion), but reduce/gather tick in
        both modes."""
        p = self._pending
        if p is None:
            return None
        prog = p.hop_progress()
        prog["epoch"] = p.epoch
        prog["overlapped_steps"] = p.overlapped_steps
        return prog

    def finalize(self) -> bool:
        """Block until an in-flight overlapped round (if any) is applied.
        Call at the end of training so the last epoch's averaging is not
        lost. Returns True iff a round was applied."""
        if self._pending is None:
            return False
        self._finish_pending(block=True)
        return True

    def drop_pending_round(self) -> None:
        """Abandon the current trajectory's swarm work WITHOUT applying
        it — the rollback paths' hook: discard an in-flight overlapped
        round AND the live gradient accumulator. Both were computed
        against pre-rollback (divergent) params; averaging either onto
        restored state would defeat the rollback (r5 review findings)."""
        self._finish_pending(block=True, discard=True)
        self._grad_acc = None
        self.local_samples = 0

    # _run_global_step exchange modes, broadcast coordinator -> followers
    # on slices whose gradients are sharded across processes
    _X_ALONE, _X_ALLREDUCE, _X_POWERSGD = 0, 1, 2

    def _run_global_step(self) -> None:
        from dalle_tpu.parallel.multihost import (broadcast_arrays,
                                                  broadcast_decision,
                                                  host_global,
                                                  is_fully_addressable)

        t0 = time.perf_counter()
        treedef = jax.tree_util.tree_structure(self._grad_acc)
        leaves = jax.tree_util.tree_leaves(self._grad_acc)
        # Gradients sharded ACROSS processes (fsdp/tp/sp slices): pulling
        # them to a host is a collective all-gather, and the PowerSGD
        # device phases are SPMD programs — every process of the slice
        # must run those paths in lockstep, with the wire exchange still
        # coordinator-only (ADVICE r2: np.asarray raises on such arrays).
        sharded = not all(is_fully_addressable(g) for g in leaves)
        weight = float(max(self.local_samples, 1))

        # single-process plain-codec peers defer the host grad pull until
        # a real group forms: an ALONE epoch applies the DEVICE grads
        # directly, and pulling ~0.5 GB of f32 through a slow
        # host<->device link dominated solo flagship epochs (r4 sustained
        # run: 100+ s/epoch of pure transfer). Multi-process slices keep
        # the eager pull — host_global is a lockstep collective that must
        # run on every process before the coordinator/follower split.
        lazy_pull = (not sharded and self._powersgd is None
                     and jax.process_count() == 1)
        if not (self.role.swarm_enabled or sharded):
            grads_local = None  # unsharded follower: broadcast only
        elif self._powersgd is not None:
            # device-side PowerSGD: the accumulated grads stay on device —
            # phase1 projects them there and only rank-r factors (plus the
            # small unplanned tail) are pulled for the wire
            grads_local: List[Any] = [g / weight for g in leaves]
        elif lazy_pull:
            grads_local = None  # pulled below iff the epoch exchanges
        else:
            grads_local = [a / weight for a in host_global(leaves)]
        t_pull = time.perf_counter()

        if not self.role.swarm_enabled:
            self._follower_exchange(treedef, leaves, grads_local, sharded)
            return

        group = make_group(
            self.dht, f"{self.cfg.run_id}_grads", self.local_epoch,
            weight=weight, matchmaking_time=self.cfg.matchmaking_time,
            min_group_size=self.matchmaking_min_group,
            client_mode=self.client_mode, authorizer=self.authorizer,
            encrypt=self.cfg.encrypt_data_plane, ledger=self.ledger)
        t_match = time.perf_counter()
        if self.tracer is not None:
            self.tracer.add(
                "swarm", "matchmaking", self._round_trace(
                    self.local_epoch), t_pull, t_match - t_pull,
                group=group.size if group is not None else 1)
        exchanging = group is not None and group.size > 1
        mode = (self._X_POWERSGD if self._powersgd is not None else
                self._X_ALLREDUCE) if exchanging else self._X_ALONE
        if sharded:
            broadcast_decision(mode)
        pull_s = t_pull - t0
        if exchanging:
            if grads_local is None:  # deferred pull: the wire needs the
                t_lazy = time.perf_counter()  # grads outside the accumulator
                if self._device_grad_handoff:
                    # device codec: the grads stay device arrays — the
                    # round flattens and quantizes them there (its one
                    # bulk host copy shows up in its flatten phase)
                    grads_local = [g / weight for g in leaves]
                else:
                    grads_local = [a / weight for a in host_global(leaves)]
                pull_s += time.perf_counter() - t_lazy  # keep attribution
            budget = min(self.cfg.allreduce_timeout,
                         max(1.0, self.cfg.averaging_timeout
                             - (time.perf_counter() - t0)))
            if mode == self._X_POWERSGD:
                from dalle_tpu.swarm.powersgd import average_with_powersgd
                averaged = average_with_powersgd(
                    self._powersgd, grads_local,
                    self._powersgd_reduce_fn(group, weight, budget,
                                             sharded),
                    epoch=self.local_epoch)
            else:
                ra = self._new_round_audit(self.local_epoch)
                rep = {} if self.tracer is not None else None
                t_ar = time.perf_counter()
                averaged = run_allreduce(
                    self.dht, group, f"{self.cfg.run_id}_grads",
                    self.local_epoch, grads_local, weight=weight,
                    allreduce_timeout=budget, codec=self._grad_codec,
                    adaptive_threshold=self.cfg.size_adaptive_threshold,
                    codec_backend=self._codec_backend, ledger=self.ledger,
                    screen=self._screen,
                    max_peer_weight=self._max_peer_weight,
                    audit=ra, gather_codec=self._gather_codec,
                    ef_scatter=self._ef_scatter,
                    ef_gather=self._ef_gather,
                    pin_codec=self._pin_codec, report=rep,
                    pipeline_hops=self._pipeline_hops,
                    pipeline_depth=self._pipeline_depth,
                    tracer=self.tracer,
                    trace=self._round_trace(self.local_epoch))
                if ra is not None:
                    self._auditor.submit(ra)
                self._trace_allreduce(
                    self._round_trace(self.local_epoch), t_ar,
                    time.perf_counter(), rep, group.size)
        else:
            # alone this epoch: with a deferred pull the grads never left
            # the device — they flow straight into the jitted apply
            averaged = (grads_local if grads_local is not None
                        else [g / weight for g in leaves])
        # deliver the averaged gradients to this slice's followers. On
        # sharded slices the PowerSGD result is already global on every
        # process (device SPMD + in-phase broadcasts) and the ALONE case
        # is each process's identical grads — only a plain all-reduce
        # result lives solely on the coordinator.
        if sharded:
            if mode == self._X_ALLREDUCE:
                averaged = broadcast_arrays(averaged, like=grads_local)
        else:
            averaged = broadcast_arrays(averaged, like=grads_local)
        t_reduce = time.perf_counter()

        self._apply_averaged(treedef, averaged)
        # per-phase timing of the collective path (SURVEY.md §5 calls for
        # per-collective timing; the reference only ever had wall-clock
        # sps). apply/state-averaging split comes from _apply_averaged so
        # state-averaging network time is not misattributed to compute.
        self.last_timings = {
            "grad_pull_s": round(pull_s, 4),
            "matchmaking_s": round(t_match - t_pull, 4),
            "allreduce_s": round(t_reduce - t_match - max(
                0.0, pull_s - (t_pull - t0)), 4),
            **self._apply_timings,
            "group_size": group.size if group else 1,
            "robust": self.robustness_snapshot(),
        }
        logger.info("global step -> epoch %d (%.2fs, group=%s, %s)",
                    self.local_epoch, time.perf_counter() - t0,
                    group.size if group else 1, self.last_timings)

    def _follower_exchange(self, treedef, leaves, grads_local,
                           sharded: bool) -> None:
        """The follower half of a slice's global step. Unsharded slices:
        just receive the coordinator's averaged gradients. Sharded slices:
        mirror the coordinator's announced mode — the PowerSGD device
        phases are SPMD collectives this process must join."""
        from dalle_tpu.parallel.multihost import (broadcast_arrays,
                                                  broadcast_decision)

        if not sharded:
            like = [np.zeros(g.shape, np.float32) for g in leaves]
            averaged = broadcast_arrays(None, like=like)
        else:
            mode = broadcast_decision(self._X_ALONE)
            if mode == self._X_POWERSGD:
                from dalle_tpu.swarm.powersgd import average_with_powersgd
                averaged = average_with_powersgd(
                    self._powersgd, grads_local,
                    self._powersgd_reduce_fn(None, 0.0, 0.0, sharded=True),
                    epoch=self.local_epoch)
            elif mode == self._X_ALLREDUCE:
                averaged = broadcast_arrays(None, like=grads_local)
            else:  # ALONE: every process already holds identical grads
                averaged = grads_local
        self._apply_averaged(treedef, averaged)
        self.last_timings = dict(self._apply_timings)

    def _powersgd_reduce_fn(self, group, weight: float, budget: float,
                            sharded: bool):
        """Reduce callback for the PowerSGD factor rounds: two rounds per
        epoch (P then Q+raw), each with half the round budget, wire on the
        coordinator only. On sharded slices the completeness flag and the
        averaged factors are broadcast so every process raises (or
        proceeds) identically — an incomplete round (member died
        mid-exchange) means the averaged factor bytes may diverge from
        other survivors' orthogonal bases, so the epoch falls back to
        local grads instead (the elasticity story)."""
        from dalle_tpu.parallel.multihost import (broadcast_arrays,
                                                  broadcast_decision)
        from dalle_tpu.swarm.powersgd import IncompleteRound

        coordinator = self.role.swarm_enabled

        def reduce_fn(tensors, phase):
            ok, out = 1, None
            if coordinator:
                rep: dict = {}
                # the factor rounds are audited like any butterfly
                # round (r16): a challenged factor-part owner serves a
                # transcript under the phase prefix, and a conviction
                # gossips a proof-carrying receipt. They are REPAIRED
                # too: a replayed-bytes-mismatch conviction queues its
                # honest-minus-served correction under this phase's
                # prefix, and the drain below patches the averaged
                # factor bytes before the compressor reconstructs from
                # them — the same pre-step-exact / bounded-staleness
                # split as gradient repair, confined to projection
                # space.
                prefix = f"{self.cfg.run_id}_grads_{phase}"
                ra = self._new_round_audit(self.local_epoch,
                                           f"grads_{phase}")
                out = run_allreduce(
                    self.dht, group, prefix,
                    self.local_epoch, tensors, weight=weight,
                    allreduce_timeout=budget / 2,
                    codec=self._grad_codec,
                    adaptive_threshold=self.cfg.size_adaptive_threshold,
                    report=rep, codec_backend=self._codec_backend,
                    ledger=self.ledger, screen=self._screen,
                    max_peer_weight=self._max_peer_weight,
                    audit=ra)
                if ra is not None:
                    self._auditor.submit(ra)
                if not rep.get("complete", False):
                    ok = 0
                if (ok and out is not None and self._repair is not None
                        and self._repair.accepts(prefix)
                        and self._repair.pending(prefix)):
                    out = [np.array(a, np.float32, copy=True)
                           for a in out]
                    self._repair.apply(out, prefix=prefix)
            if sharded:
                ok = broadcast_decision(ok)
            if not ok:
                raise IncompleteRound(phase)
            if sharded:
                out = broadcast_arrays(out, like=tensors)
            return out

        return reduce_fn

    def _note_epoch_advanced(self) -> None:
        """Every epoch advance (global step or peer-state load) drives
        the health ledger's strike decay and the chaos layer's
        crash-at-epoch trigger (ChaosDHT.note_epoch — a no-op attribute
        miss on a plain DHT)."""
        if self.ledger is not None:
            self.ledger.advance_epoch(self.local_epoch)
        note = getattr(self.dht, "note_epoch", None) \
            if self.dht is not None else None
        if note is not None:
            note(self.local_epoch)

    def _apply_averaged(self, treedef, averaged,
                        preserve_accumulator: bool = False) -> None:
        """The post-exchange half of a global step, identical on every
        process of a slice: apply the averaged gradients, advance the
        epoch, and run the (broadcast-synchronized) state averaging.
        Fills ``self._apply_timings`` with the apply/state-averaging
        split. ``preserve_accumulator`` (overlapped rounds): the live
        accumulator holds the NEXT epoch's gradients collected during the
        round — it must survive the reconcile."""
        t0 = time.perf_counter()
        from dalle_tpu.parallel.multihost import process_count
        grads_prefix = f"{self.cfg.run_id}_grads"
        if (self._repair is not None
                and self._repair.pending(grads_prefix)
                and process_count() == 1):
            # Round repair (swarm/repair.py): drain queued corrections
            # into the vector this step applies. A correction whose
            # round is THIS application's round still finds the served
            # bytes in place and is assigned exactly (bit-identical to
            # an honest round); one that missed its round rides this
            # later step as a bounded-staleness compensation. Single-
            # process peers only — a multi-host slice would need the
            # correction broadcast to stay in lockstep, and its
            # followers run no auditor to agree with. Drained under the
            # grads prefix only (r20): factor/state corrections land at
            # their own phase's application site, never here.
            averaged = [np.array(a, np.float32, copy=True)
                        for a in averaged]
            self._repair.apply(averaged, prefix=grads_prefix)
        grads_tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(a) for a in averaged])
        self.state = self.apply_step(self.state, grads_tree)
        jax.block_until_ready(jax.tree_util.tree_leaves(self.state.params)[0])
        t_applied = time.perf_counter()

        epoch0 = self.local_epoch
        self.local_epoch += 1
        if not preserve_accumulator:
            self.local_samples = 0
            self._grad_acc = None
        self.tracker.reset_epoch(self.local_epoch)
        self._note_epoch_advanced()

        if (self.cfg.average_state_every > 0
                and self.local_epoch % self.cfg.average_state_every == 0):
            self._average_state()
        self._apply_timings = {
            "apply_s": round(t_applied - t0, 4),
            "state_avg_s": round(time.perf_counter() - t_applied, 4),
        }
        if self.tracer is not None:
            trace = self._round_trace(epoch0)
            self.tracer.add("swarm", "apply", trace, t0,
                            self._apply_timings["apply_s"])
            if self._apply_timings["state_avg_s"] > 0:
                self.tracer.add("swarm", "state_avg", trace, t_applied,
                                self._apply_timings["state_avg_s"])
            self.tracer.maybe_flush()

        for cb in self.on_after_global_step:
            cb()

    def robustness_snapshot(self) -> dict:
        """The silent robustness counters, surfaced (r16): audit
        volume and verdicts, repairs applied (exact vs stale), repair-
        ring evictions, proof-receipt traffic, and the r15 error-
        feedback lost-residual windows — everything that was log-only
        before. Rides the per-step round report (``last_timings
        ["robust"]``) and the swarm metrics record (training loop)."""
        out = {
            "parts_audited": 0, "audit_fail": 0, "audit_omit": 0,
            "audit_unserved": 0, "ring_evictions": 0,
            "repairs_applied": 0, "repairs_exact": 0,
            "repairs_pending": 0,
            "proofs_published": 0, "proofs_convicted": 0,
            "proofs_rejected": 0, "proofs_by_reference": 0,
            "proof_fetch_attempted": 0, "proof_fetch_ok": 0,
            "proof_fetch_failed": 0, "proof_fetch_timeouts": 0,
            "proof_fetch_failover": 0, "proof_fetch_bytes": 0,
            "ef_lost_rounds": 0,
        }
        if self._auditor is not None:
            # one locked snapshot, not five bare attribute reads racing
            # the audit thread's increments
            ac = self._auditor.counters()
            out["parts_audited"] = ac["audited"]
            out["audit_fail"] = ac["failures"]
            out["audit_omit"] = ac["omissions"]
            out["audit_unserved"] = ac["unserved"]
            out["ring_evictions"] = ac["ring_evictions"]
        if self._repair is not None:
            snap = self._repair.snapshot()
            out["repairs_applied"] = snap["applied"]
            out["repairs_exact"] = snap["applied_exact"]
            out["repairs_pending"] = snap["pending"]
        if self._gossip is not None:
            out["proofs_published"] = self._gossip.proofs_published
            out["proofs_convicted"] = self._gossip.proofs_convicted
            out["proofs_rejected"] = self._gossip.proofs_rejected
            out["proofs_by_reference"] = self._gossip.proofs_by_reference
        if self._evidence is not None:
            for k, v in self._evidence.counters().items():
                out[f"proof_fetch_{k}"] = v
        for ef in (self._ef_scatter, self._ef_gather):
            if ef is not None:
                out["ef_lost_rounds"] += ef.lost_rounds
        return out

    # -- drift control / recovery ----------------------------------------

    def _average_state(self) -> None:
        """Butterfly-average the float content of the state (params + opt
        statistics).

        Block-quantized moments are dequantized before averaging and
        requantized after: averaging their absmax scales against another
        peer's codes would corrupt the moments precisely in the divergent-
        peer situation state averaging exists for. Integer step counters
        stay local (identical updates keep them synchronized)."""
        from dalle_tpu.ops.quant import (Quantized, dequantize_blockwise,
                                         quantize_blockwise)
        from dalle_tpu.parallel.multihost import (broadcast_arrays,
                                                  broadcast_decision,
                                                  host_global,
                                                  is_fully_addressable)

        # the epoch condition that got us here is deterministic, so every
        # process of a slice enters together; whether a swarm group formed
        # is the coordinator's knowledge and must be broadcast
        tree = (self.state.params, self.state.opt_state)
        is_q = lambda x: isinstance(x, Quantized)  # noqa: E731

        def float_leaves():
            # dequantizing every 8-bit moment + f32-copying every float
            # leaf is model-sized host work: build it only on paths that
            # will actually average (a lone peer skips it entirely).
            # host_global + the dequant jit are collectives for state
            # sharded across processes — see the lockstep hoist below.
            leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_q)
            float_idx, to_pull = [], []
            for i, leaf in enumerate(leaves):
                if is_q(leaf):
                    float_idx.append(i)
                    to_pull.append(dequantize_blockwise(leaf))
                elif compression.is_float_dtype(
                        getattr(leaf, "dtype", np.asarray(leaf).dtype)):
                    float_idx.append(i)
                    to_pull.append(leaf)
            floats = [a.astype(np.float32, copy=False)
                      for a in host_global(to_pull)]
            return leaves, float_idx, floats

        def _addressable(leaf):
            if is_q(leaf):
                return (is_fully_addressable(leaf.codes)
                        and is_fully_addressable(leaf.absmax))
            return is_fully_addressable(leaf)

        averaged = leaves = float_idx = floats = None
        state_sharded = not all(
            _addressable(x)
            for x in jax.tree_util.tree_leaves(tree, is_leaf=is_q))
        if state_sharded:
            # sharded slices must run the collective pull on every process
            # in lockstep, BEFORE the coordinator disappears into
            # matchmaking (followers would otherwise deadlock inside the
            # all-gather while the coordinator owns the wire)
            leaves, float_idx, floats = float_leaves()
        if self.role.swarm_enabled:
            group = make_group(
                self.dht, f"{self.cfg.run_id}_state", self.local_epoch,
                weight=1.0, matchmaking_time=self.cfg.matchmaking_time,
                min_group_size=self.matchmaking_min_group,
                client_mode=self.client_mode, authorizer=self.authorizer,
                encrypt=self.cfg.encrypt_data_plane)
            if group is not None and group.size > 1:
                if floats is None:
                    leaves, float_idx, floats = float_leaves()
                # state averaging is audited under its own prefix
                # (r16): a hostile owner serving a wrong averaged
                # STATE part — the one attack that poisons params
                # directly, bypassing every gradient defense — now
                # faces the same transcript/replay conviction, and
                # the proof receipt convicts peers that skipped this
                # averaging round entirely
                ra = self._new_round_audit(self.local_epoch, "state")
                averaged = run_allreduce(
                    self.dht, group, f"{self.cfg.run_id}_state",
                    self.local_epoch, floats, weight=1.0,
                    allreduce_timeout=self.cfg.allreduce_timeout,
                    codec=self._state_codec,
                    adaptive_threshold=self.cfg.size_adaptive_threshold,
                    codec_backend=self._codec_backend,
                    ledger=self.ledger, screen=self._screen,
                    max_peer_weight=self._max_peer_weight,
                    audit=ra)
                if ra is not None:
                    self._auditor.submit(ra)
                state_prefix = f"{self.cfg.run_id}_state"
                if (averaged is not None and self._repair is not None
                        and self._repair.accepts(state_prefix)
                        and self._repair.pending(state_prefix)):
                    # r20 aux repair: a convicted state-averaging round
                    # queues its correction under the state prefix;
                    # drain it into the averaged floats BEFORE the
                    # requantize/adopt below so the repaired bytes are
                    # what lands in params/moments (pre-step exact when
                    # this is the convicted round itself, bounded-
                    # staleness compensation otherwise)
                    averaged = [np.array(a, np.float32, copy=True)
                                for a in averaged]
                    self._repair.apply(averaged, prefix=state_prefix)
        if not broadcast_decision(0 if averaged is None else 1):
            return
        if floats is None:  # follower of a slice whose coordinator averaged
            leaves, float_idx, floats = float_leaves()
        averaged = broadcast_arrays(averaged, like=floats)
        new_leaves = list(leaves)
        for i, avg in zip(float_idx, averaged):
            old = leaves[i]
            if is_q(old):
                requant = quantize_blockwise(
                    jnp.asarray(avg.reshape(old.shape)),
                    block_size=old.codes.shape[1], signed=old.signed)
                # keep the mesh placement (sharded moments must stay
                # sharded or the next jitted step recompiles/replicates)
                new_leaves[i] = type(old)(
                    codes=jax.device_put(requant.codes, old.codes.sharding),
                    absmax=jax.device_put(requant.absmax,
                                          old.absmax.sharding),
                    shape=old.shape, signed=old.signed)
            else:
                arr = jnp.asarray(avg.reshape(old.shape)).astype(old.dtype)
                new_leaves[i] = jax.device_put(
                    arr, old.sharding) if hasattr(old, "sharding") \
                    else jax.device_put(arr)
        treedef = jax.tree_util.tree_structure(tree, is_leaf=is_q)
        params, opt_state = jax.tree_util.tree_unflatten(treedef, new_leaves)
        self.state = self.state.replace(params=params, opt_state=opt_state)

    def load_state_from_peers(self, min_epoch: int = 0,
                              timeout: Optional[float] = None) -> bool:
        """Bootstrap params+opt state from the freshest live peer
        (reference callback.py:41, run_aux_peer.py:48). In a multi-host
        slice the coordinator downloads and broadcasts; every process
        adopts the identical state."""
        from dalle_tpu.parallel.multihost import (broadcast_arrays,
                                                  broadcast_decision)

        # an in-flight overlapped round averages gradients for state this
        # download is about to replace: drain and discard it first
        self._finish_pending(block=True, discard=True)

        epoch, arrays = -1, None
        if self.role.swarm_enabled:
            result = load_state_from_peers(
                self.dht, self.cfg.run_id, min_epoch=min_epoch,
                timeout=timeout or self.cfg.averaging_timeout,
                tracer=self.tracer)
            if result is None:
                logger.warning("load_state_from_peers: nobody answered")
            else:
                epoch, arrays = result
                # accept only state that moves us forward; same-epoch
                # state would wipe the gradient accumulator for nothing
                # (except at epoch 0, where a fresh joiner synchronizes
                # its random init with the swarm)
                if epoch < self.local_epoch or (
                        epoch == self.local_epoch and self.local_epoch > 0):
                    logger.warning(
                        "ignoring stale peer state (epoch %d <= local %d)",
                        epoch, self.local_epoch)
                    epoch, arrays = -1, None
        # broadcast_one_to_all needs identical shapes/dtypes on every
        # process: canonicalize the downloaded (wire-format) arrays to the
        # local state's layout before the broadcast decision. Only shapes/
        # dtypes are needed (a zeros template), NOT the values — pulling
        # the values would be a model-sized collective that followers
        # would enter while the coordinator is still inside the download
        # loop (the lockstep-before-wire rule of _average_state).
        like = [np.zeros(x.shape, np.dtype(getattr(x, "dtype", np.float32)))
                for x in jax.tree_util.tree_leaves(
                    (self.state.params, self.state.opt_state))]
        if arrays is not None:
            try:
                assert len(arrays) == len(like)
                arrays = [np.asarray(a).reshape(np.asarray(l).shape)
                          .astype(np.asarray(l).dtype)
                          for a, l in zip(arrays, like)]
            except Exception:  # noqa: BLE001 - structurally alien state
                logger.warning("peer state does not match local structure")
                epoch, arrays = -1, None
        epoch = broadcast_decision(epoch if arrays is not None else -1)
        if epoch < 0:
            return False
        arrays = broadcast_arrays(arrays, like=like)
        self._replace_state_leaves(arrays)
        self.local_epoch = max(epoch, self.local_epoch)
        self.local_samples = 0
        self._grad_acc = None
        self.tracker.reset_epoch(self.local_epoch)
        self._note_epoch_advanced()
        for cb in self.on_load_state_from_peers:
            cb()
        return True

    def shutdown(self) -> None:
        self._finish_pending(block=True, discard=True)
        if self._server is not None:
            self._server.stop()
            self._server = None
        if self._gossip is not None:
            # signal AND bounded-join BEFORE the caller tears the DHT
            # down: an in-flight publish/fold on a destroyed native
            # node is a use-after-free (dht.shutdown ordering contract)
            self._gossip.stop()
            self._gossip = None
        if self._evidence is not None:
            # after the gossip worker (its publish path posts through
            # this plane), before the DHT dies (same ordering contract:
            # an in-flight evidence fetch needs a live node)
            self._evidence.stop()
            self._evidence = None
        if self._auditor is not None:
            # same ordering contract: an in-flight transcript fetch on
            # a destroyed native node is a use-after-free
            self._auditor.stop()
            self._auditor = None
        if self.tracer is not None:
            # the trace from a crashed run is the artifact you want most
            self.tracer.flush()

    def __enter__(self) -> "CollaborativeOptimizer":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
