"""Sustained flagship training run on the real chip (VERDICT r3 weak #4).

Runs the PRODUCTION path — TrainingTask -> train_loop (warmup self-check,
jitted accumulate grad step, collaborative optimizer in solo mode,
NaN sweep + rollback, rolling checkpoints) — at the tuned operating
point (micro 4 x accum 64, remat skip 1, fused plain-block FF, 8-bit
LAMB) on synthetic shard data for a wall-clock budget, logging one JSONL
line per global step: the loss curve, step-time variance, NaN/rollback
count and checkpoint cadence the reference's operators read off their
wandb dashboards (SURVEY.md section 4).

Run:  python scripts/sustained_run.py [minutes] [out_prefix] \
          [data_dir] [tokenizer_path] [warmup_steps] [total_steps]
(data_dir/tokenizer_path: prepared shards through the production
CodesDataset — pair with ``prepare_data synthetic-shards --structured``
for the learning-proof run; warmup/total size the LR schedule to the
run length instead of the reference's 31250-step production schedule.)
Artifacts: {prefix}.jsonl (per-step log) + {prefix}.json (driver-readable
summary line).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    minutes = float(sys.argv[1]) if len(sys.argv) > 1 else 40.0
    prefix = sys.argv[2] if len(sys.argv) > 2 else "SUSTAINED_RUN"
    # optional: a prepared shard directory + tokenizer (the production
    # data pipeline; pair with prepare_data synthetic-shards --structured
    # for the learning-proof run, VERDICT r4 next #4)
    data_dir = sys.argv[3] if len(sys.argv) > 3 else None
    tokenizer_path = sys.argv[4] if len(sys.argv) > 4 else None
    # LR schedule sized to the RUN, not to the reference's 31250-step
    # production schedule: a 55-minute run lives entirely inside the
    # 3125-step warmup (lr <= 5e-5 throughout — the r4 runs' loss could
    # not move decisively regardless of the data). Defaults keep the r4
    # production schedule; the learning-proof run passes ~[20, 300].
    warmup_steps = int(sys.argv[5]) if len(sys.argv) > 5 else 3125
    total_steps = int(sys.argv[6]) if len(sys.argv) > 6 else 31250

    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from dalle_tpu.config import (CollabConfig, OptimizerConfig,
                                  PeerConfig, TrainerConfig,
                                  flagship_model_config)
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    model = flagship_model_config()
    trainer = TrainerConfig(per_device_batch=4, grad_accum_steps=64)
    # solo peer: every 256-sample local step completes a swarm epoch, so
    # the LAMB apply + NaN sweep + checkpoint cadence all exercise
    # matchmaking_time: a SOLO peer waits out the whole window every
    # epoch before proceeding alone; 3 s keeps the cadence honest without
    # spending a third of the run in an empty lobby
    collab = CollabConfig(run_id="sustained", target_batch_size=256,
                          matchmaking_time=3.0, average_state_every=0)
    # a solo FULL peer: swarm of one, every epoch takes the ALONE path
    # (LAMB apply + sweep + checkpoints all run; no wire traffic)
    task = TrainingTask(model,
                        OptimizerConfig(warmup_steps=warmup_steps,
                                        total_steps=total_steps),
                        trainer, collab,
                        PeerConfig(), data_path=data_dir,
                        tokenizer_path=tokenizer_path)

    # count NaN rollbacks (train_loop reports them via logging)
    import logging

    rollbacks = {"n": 0}

    class _RollbackCounter(logging.Handler):
        def emit(self, record):
            if "rolling back" in record.getMessage():
                rollbacks["n"] += 1

    logging.getLogger("dalle_tpu.training.loop").addHandler(
        _RollbackCounter())
    logging.basicConfig(level=logging.INFO)

    log_path = f"{prefix}.jsonl"
    log = open(log_path, "w")
    t_start = time.monotonic()
    deadline = t_start + minutes * 60
    state = {"steps": 0, "last_t": None, "step_times": [],
             "losses": [], "epochs_seen": set(),
             "hidden_s": [], "overlapped_steps": []}

    def on_epoch(rep):
        now = time.monotonic()
        dt = None if state["last_t"] is None else now - state["last_t"]
        state["last_t"] = now
        if dt is not None:
            state["step_times"].append(dt)
        state["losses"].append(rep.loss)
        state["epochs_seen"].add(rep.epoch)
        state["steps"] += 1
        # overlapped-round telemetry (delay_optimizer_step, r5): how much
        # swarm-round wall was hidden behind training this epoch
        timings = dict(task.collab_optimizer.last_timings)
        if "hidden_s" in timings:
            state["hidden_s"].append(timings["hidden_s"])
            state["overlapped_steps"].append(
                timings.get("overlapped_steps", 0))
        log.write(json.dumps({
            "t_s": round(now - t_start, 1),
            "epoch": rep.epoch,
            "loss": round(rep.loss, 4),
            "samples_per_s": round(rep.samples_per_second, 2),
            "step_s": None if dt is None else round(dt, 2),
            "timings": timings,
        }) + "\n")
        log.flush()
        if now >= deadline:
            raise KeyboardInterrupt  # budget reached: clean stop

    ckpt_dir = os.path.abspath(f"{prefix}_ckpt")
    try:
        # backup cadence 5: each backup serializes ~1.2 GB of state
        # (~2 min over the r4 run's slow host link); every-epoch
        # backups would halve the run's step count
        train_loop(task, warmup_steps=2, on_epoch=on_epoch,
                   publish_metrics_records=False,
                   checkpoint_dir=ckpt_dir, save_every=10,
                   backup_every=5)
    except KeyboardInterrupt:
        pass
    finally:
        task.shutdown()
        log.close()

    import numpy as np

    losses = np.array(state["losses"])
    times = np.array(state["step_times"]) if state["step_times"] else \
        np.array([0.0])
    n = len(losses)
    ckpts = sorted(os.listdir(ckpt_dir)) if os.path.isdir(ckpt_dir) else []
    summary = {
        "metric": "dalle-1.3b sustained run (tpu, tuned operating point)",
        "wall_minutes": round((time.monotonic() - t_start) / 60, 1),
        "global_steps": n,
        "samples_per_step": 256,
        "first_loss": round(float(losses[0]), 4) if n else None,
        "last_loss": round(float(losses[-1]), 4) if n else None,
        "mean_last5_loss": round(float(losses[-5:].mean()), 4) if n else
        None,
        "loss_monotone_trend": bool(n >= 4 and losses[-3:].mean()
                                    < losses[:3].mean()),
        "step_s_median": round(float(np.median(times)), 2),
        "step_s_p95": round(float(np.percentile(times, 95)), 2),
        "step_s_cv": round(float(times.std() / max(times.mean(), 1e-9)),
                           4),
        "images_per_sec_chip": round(256 / float(np.median(times)), 3)
        if times.mean() > 0 else None,
        "nan_rollbacks": rollbacks["n"],
        "checkpoints": ckpts,
        "log": log_path,
        "data": data_dir or "synthetic-affine (in-memory)",
        "lr_schedule": {"warmup_steps": warmup_steps,
                        "total_steps": total_steps},
        # overlapped-round telemetry: epochs whose swarm round ran on the
        # background thread, the wall they hid, and the grad steps that
        # executed during those windows (VERDICT r4 next #1's artifact)
        "overlapped_epochs": len(state["hidden_s"]),
        "mean_hidden_s": round(float(np.mean(state["hidden_s"])), 2)
        if state["hidden_s"] else None,
        "mean_overlapped_grad_steps": round(
            float(np.mean(state["overlapped_steps"])), 2)
        if state["overlapped_steps"] else None,
    }
    line = json.dumps(summary)
    print(line, flush=True)
    with open(f"{prefix}.json", "w") as f:
        f.write(line + "\n")


if __name__ == "__main__":
    main()
