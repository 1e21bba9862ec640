"""The trainer peer's host loop: warmup self-check, then accumulate/step.

Capability parity with the reference's hand-rolled TPU host loop
(``run_trainer_tpu.py:47-91``): 3 warmup steps validate compile + data flow
before joining the swarm; then forever: draw a batch, run the jitted
grad step, hand the gradients to the collaborative optimizer, and do
per-epoch bookkeeping (metrics publish, checkpoints) through callbacks.
The reference's "copy grads -> hivemind step -> push params" seam
(``run_trainer_tpu.py:85-88``) collapses here to
``grad_step -> collab.step``: gradients stay on device until the swarm
round needs them on the host.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from dalle_tpu.swarm.metrics import LocalMetrics, publish_metrics
from dalle_tpu.task import TrainingTask
from dalle_tpu.training.steps import grad_reduction_plan

logger = logging.getLogger(__name__)


class EpochReport:
    """What the loop knows at the end of a global step."""

    def __init__(self, epoch: int, loss: float, mini_steps: int,
                 samples_per_second: float):
        self.epoch = epoch
        self.loss = loss
        self.mini_steps = mini_steps
        self.samples_per_second = samples_per_second


def warmup(task: TrainingTask, steps: int = 3) -> float:
    """Compile + run the grad step a few times before joining the swarm
    (the reference's explicit warmup, ``run_trainer_tpu.py:47-57``).
    Returns the last warmup loss; raises if it is not finite."""
    batches = task.batches()
    params = task.collab_optimizer.state.params
    loss = float("nan")
    with task.tracer.span("train", "setup/warmup", "setup", steps=steps,
                          grad_reduction=grad_reduction_plan(task.mesh)
                          ) as span:
        task.memory.read("setup/warmup opened")
        for i in range(steps):
            t0 = time.monotonic()
            batch = next(batches)
            grads, metrics = task.grad_step(params, batch)
            jax.block_until_ready(grads)
            loss = float(metrics["loss"])
            logger.info("warmup %d/%d: loss=%.4f (%.2fs)",
                        i + 1, steps, loss, time.monotonic() - t0)
        # the step is traced by now: the model says what its layers
        # lowered to (attn_layout, layer_loop, ...), the memory account
        # what the trainer's trees hold of the device
        span.set(**task.family.engagement_records(task.model_cfg,
                                                  task.mesh))
        if steps:
            span.set(memory_layout=task.memory.step_traced((grads, metrics),
                                                           batch))
        task.memory.read("setup/warmup ran")
    if not np.isfinite(loss):
        raise RuntimeError(f"warmup produced non-finite loss {loss}")
    # warmup gradients are discarded; the tracker timer starts fresh
    task.collab_optimizer.tracker.performance_ema.reset_timer()
    return loss


def train_loop(task: TrainingTask,
               max_epochs: Optional[int] = None,
               max_steps: Optional[int] = None,
               warmup_steps: int = 3,
               publish_metrics_records: bool = True,
               on_epoch: Optional[Callable[[EpochReport], None]] = None,
               on_step: Optional[Callable[[int, float], None]] = None,
               checkpoint_dir: Optional[str] = None,
               save_every: int = 10,
               backup_every: int = 1,
               keep_checkpoints: int = 3,
               profile_dir: Optional[str] = None,
               profile_steps: tuple = (2, 6)
               ) -> List[EpochReport]:
    """Run the peer until ``max_epochs`` global steps (None = forever).

    With ``checkpoint_dir``: resume from the freshest local checkpoint on
    start (reference ``run_trainer.py:55-56``), write a rolling backup
    every ``backup_every`` epochs and a numbered checkpoint every
    ``save_every`` (``callback.py:102-113``), sweep the params for
    NaN/Inf after every global step and roll back to the backup on
    corruption (``callback.py:95-100,50-54``).

    With ``profile_dir``: capture a JAX profiler trace (TensorBoard /
    Perfetto readable) of local steps ``profile_steps[0]..[1]`` — the
    instrumentation the reference never had (SURVEY.md §5 "Tracing:
    none in-repo"; its only signal was wall-clock sps).

    Returns the per-epoch reports (for tests and the CLI's summary).
    """
    from dalle_tpu.training.checkpoint import (CheckpointManager,
                                               params_are_finite)

    from dalle_tpu.parallel import multihost

    collab = task.collab_optimizer
    coordinator = collab.role.swarm_enabled
    ckpt = None
    if checkpoint_dir is not None and coordinator:
        # multi-host slices: only the coordinator touches the checkpoint
        # directory; its (restored or fresh) state is broadcast below
        ckpt = CheckpointManager(checkpoint_dir, keep=keep_checkpoints)
        restored = ckpt.restore_latest(collab.state)
        if restored is not None:
            state, epoch = restored
            collab.state = state
            collab.local_epoch = max(collab.local_epoch, epoch)
            collab.tracker.reset_epoch(collab.local_epoch)
            logger.info("resumed from local checkpoint at epoch %d", epoch)
            # if the swarm is ahead, the straggler-resync path in
            # collab.step() will still pull fresher state from peers
    if multihost.process_count() > 1:
        # align every process of the slice on the coordinator's initial
        # state (fresh init is seed-identical, but a checkpoint restore
        # or prior swarm sync is the coordinator's alone)
        leaves = collab._state_leaves()
        leaves = multihost.broadcast_arrays(
            leaves if coordinator else None, like=leaves)
        collab._replace_state_leaves(leaves)
        collab.local_epoch = multihost.broadcast_decision(
            collab.local_epoch)
        collab.tracker.reset_epoch(collab.local_epoch)
    reports: List[EpochReport] = []
    loss_sum, mini_steps, local_steps = 0.0, 0, 0
    profiler = _StepProfiler(profile_dir, profile_steps)
    batches = task.batches()
    # the loop times itself (OBSERVABILITY.md, plane "train"): one
    # loop/step span a step, its parts as children; what the children do
    # not cover is the loop's own time. The late-step recorder opens and
    # closes the step's span, and says why a step that ran over did
    span = functools.partial(task.tracer.span, "train")
    step_attributes = task.family.step_attributes(task.model_cfg)
    late, memory = task.late_steps, task.memory
    try:
        if warmup_steps:
            warmup(task, warmup_steps)
        memory.start()
        late.start()
        while ((max_epochs is None or collab.local_epoch < max_epochs)
               and (max_steps is None or local_steps < max_steps)):
            profiler.tick(local_steps)
            with late.step(local_steps + 1) as step_row:
                with span("loop/batch_fetch"):
                    batch = next(batches)
                with span("loop/grad_dispatch"):
                    grads, metrics = task.grad_step(collab.state.params,
                                                    batch)
                memory.after_grad((grads, metrics), batch)
                with span("loop/loss_wait"):
                    loss = float(metrics["loss"])
                    # what the model counts a step (an expert layer's
                    # load), read with the loss from the step's aux
                    step_row.set(**{k: float(metrics[k])
                                    for k in step_attributes})
                memory.settled()
                loss_sum += loss
                mini_steps += 1
                local_steps += 1
                if on_step is not None:
                    with span("loop/hook"):
                        on_step(local_steps, loss)

                epoch_before = collab.local_epoch
                did_global = collab.step(grads,
                                         batch_size=task.local_batch_size)
                # hop-granular round visibility (r19): while an overlapped
                # round is in flight the loop keeps accumulating — surface
                # which parts have already landed instead of one opaque
                # "round pending" wall (debug level: this fires every step)
                if logger.isEnabledFor(logging.DEBUG):
                    prog = collab.round_progress()
                    if prog is not None:
                        logger.debug(
                            "round in flight (epoch %d): scatter=%d "
                            "reduce=%d gather=%d parts done, %d grad steps "
                            "overlapped", prog["epoch"], prog["scatter"],
                            prog["reduce"], prog["gather"],
                            prog["overlapped_steps"])
                rolled_back = False
                if did_global and ckpt is not None:
                    epoch = collab.local_epoch
                    try:
                        if not params_are_finite(collab.state.params):
                            logger.warning(
                                "non-finite params after epoch %d: rolling "
                                "back to the local backup", epoch)
                            # a round launched in the same step() that
                            # reconciled the NaN-producing apply carries the
                            # divergent trajectory's gradients: discard it
                            # before restoring (never apply it post-rollback)
                            collab.drop_pending_round()
                            restored = ckpt.restore_backup(collab.state)
                            if restored is None:
                                restored = ckpt.restore_latest(collab.state)
                            if restored is None:
                                raise RuntimeError(
                                    "params corrupted and no backup to "
                                    "restore")
                            collab.state, backup_epoch = restored
                            collab.local_epoch = backup_epoch
                            collab.tracker.reset_epoch(backup_epoch)
                            rolled_back = True
                        else:
                            do_backup = (backup_every
                                         and epoch % backup_every == 0)
                            if save_every and epoch % save_every == 0:
                                ckpt.save(collab.state, epoch,
                                          backup=do_backup)
                            elif do_backup:
                                ckpt.save_backup(collab.state, epoch)
                    except BaseException:
                        # a coordinator dying between the global step and the
                        # rollback broadcast would wedge every follower inside
                        # broadcast_decision forever: send the abort code
                        # first, then re-raise
                        if multihost.process_count() > 1:
                            multihost.broadcast_decision(2)
                        raise
                if did_global and multihost.process_count() > 1:
                    # a coordinator-side NaN rollback must re-align followers;
                    # code 2 = the coordinator failed and is going down
                    rb = multihost.broadcast_decision(1 if rolled_back else 0)
                    if rb == 2:
                        raise RuntimeError(
                            "slice coordinator failed during checkpoint "
                            "handling")
                    if rb == 1:
                        leaves = collab._state_leaves()
                        leaves = multihost.broadcast_arrays(
                            leaves if coordinator else None, like=leaves)
                        collab._replace_state_leaves(leaves)
                        collab.local_epoch = multihost.broadcast_decision(
                            collab.local_epoch)
                        collab.tracker.reset_epoch(collab.local_epoch)
                if collab.local_epoch != epoch_before:
                    # global step OR resync-from-peers: either way a new
                    # epoch
                    with span("loop/epoch_report"):
                        report = EpochReport(
                            epoch=collab.local_epoch,
                            loss=loss_sum / max(mini_steps, 1),
                            mini_steps=mini_steps,
                            samples_per_second=collab.tracker
                            .performance_ema.samples_per_second)
                        reports.append(report)
                        _announce_epoch(
                            task, report,
                            publish=(did_global and publish_metrics_records
                                     and coordinator))
                        if on_epoch is not None:
                            on_epoch(report)
                    loss_sum, mini_steps = 0.0, 0
                memory.close_step(step_row)
            if local_steps == 1:
                # set-up ends where the first step closes: where its
                # seconds went, once (obs/compiles.py)
                task.compiles.account_setup()
        # an overlapped round (delay_optimizer_step) may still be in
        # flight when the loop exits: apply it rather than lose the
        # epoch's averaging (shutdown() would discard it) — EXCEPT when
        # the epoch budget is already spent (the same-call relaunch can
        # leave a round for epoch max_epochs+1 pending; applying it
        # would overshoot the caller's contract)
        if (max_epochs is not None
                and collab.local_epoch >= max_epochs):
            collab.drop_pending_round()
        elif collab.finalize():
            if mini_steps > 0:
                # with zero grad steps since the last report (the round
                # launched in the same call that reconciled its
                # predecessor), there is no honest loss to attach — the
                # apply still happened, only the report is skipped
                reports.append(EpochReport(
                    epoch=collab.local_epoch,
                    loss=loss_sum / mini_steps,
                    mini_steps=mini_steps,
                    samples_per_second=(
                        collab.tracker.performance_ema.samples_per_second)))
            if ckpt is not None and params_are_finite(collab.state.params):
                ckpt.save_backup(collab.state, collab.local_epoch)
    except Exception as exc:
        # a chip that is too small says what held it (one memory/exhausted
        # record, one ERROR), and the runtime's own error goes on up
        memory.exhausted(exc)
        raise
    finally:
        late.stop()
        # the trace from a crashed run is the artifact you want most
        profiler.close()
        if ckpt is not None:
            ckpt.close()  # drain async checkpoint writes before returning
    return reports


def _announce_epoch(task: TrainingTask, report: EpochReport,
                    publish: bool) -> None:
    """Publish this peer's signed metrics record for the epoch (the
    coordinator, after a global step) and log the epoch line."""
    collab = task.collab_optimizer
    if publish:
        robust = collab.robustness_snapshot()
        publish_metrics(
            task.dht, task.peer_cfg.experiment_prefix,
            LocalMetrics(
                peer_id=task.dht.peer_id,
                epoch=report.epoch,
                samples_per_second=report.samples_per_second,
                samples_accumulated=0,
                loss=report.loss,
                mini_steps=report.mini_steps,
                parts_audited=robust["parts_audited"],
                audit_convictions=(robust["audit_fail"]
                                   + robust["audit_omit"]),
                repairs_applied=robust["repairs_applied"],
                repair_ring_evictions=robust["ring_evictions"],
                ef_lost_rounds=robust["ef_lost_rounds"],
                proofs_published=robust["proofs_published"],
                proofs_convicted=robust["proofs_convicted"],
                proofs_rejected=robust["proofs_rejected"]),
            expiration=task.collab_cfg.metrics_expiration)
    logger.info(
        "epoch %d: mean_loss=%.4f mini_steps=%d sps=%.1f%s",
        report.epoch, report.loss, report.mini_steps,
        report.samples_per_second,
        (" hops=%s" % (collab.last_timings["round_hops"],)
         if "round_hops" in collab.last_timings else ""))


class _StepProfiler:
    """Start/stop a JAX profiler trace over a window of local steps; a
    close() in the loop's ``finally`` finalizes the trace even when the
    run dies mid-window."""

    def __init__(self, profile_dir: Optional[str], steps: tuple):
        self.dir = profile_dir
        self.first, self.last = steps
        self.active = False

    def tick(self, local_step: int) -> None:
        if self.dir is None:
            return
        if local_step == self.first and not self.active:
            jax.profiler.start_trace(self.dir)
            self.active = True
        elif local_step >= self.last and self.active:
            self._stop()

    def close(self) -> None:
        if self.active:
            self._stop()

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self.active = False
        logger.info("profiler trace written to %s", self.dir)
