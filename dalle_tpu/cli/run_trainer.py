"""Trainer peer CLI: join the swarm and train.

Capability parity with the reference's volunteer entry points
(``run_trainer.py:26-56`` and the TPU host loop ``run_trainer_tpu.py:26-91``):
parse the three-axis config split, assemble the task, print the connection
banner with a copyable ``--initial-peers`` line (``utils.py:39-56``), run the
3-step warmup self-check, then the accumulate -> swarm-step loop forever
(bounded by ``--max-epochs``/``--max-steps`` for tests and benchmarks).

Usage::

    python -m dalle_tpu.cli.run_trainer --preset tiny            # first peer
    python -m dalle_tpu.cli.run_trainer --preset tiny \
        --initial-peers 127.0.0.1:31337                          # joiner
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
from typing import Optional, Sequence

from dalle_tpu.config import (AfmoeLMConfig, CollabConfig, JoyAILMConfig,
                              KeyeLMConfig, Lfm2MoeLMConfig, ModelConfig,
                              NemotronHLMConfig, OptimizerConfig, OuroLMConfig,
                              PeerConfig, Qwen3NextLMConfig, SparseLMConfig,
                              TrainerConfig, flagship_model_config,
                              joyaiflash_model_config,
                              keyevl2_model_config, lfm2moe_model_config,
                              ouro2b6_model_config, qwen3next80b_model_config,
                              smallthinker21b_model_config,
                              tiny_model_config, trinitymini_model_config,
                              twotower30b_model_config, xl_model_config)
from dalle_tpu.cli._args import (add_dataclass_args, check_no_collisions,
                                 dataclass_from_args)

logger = logging.getLogger("dalle_tpu.trainer")

MODEL_PRESETS = {
    # the 1.3B (task.py:62-83) with config.FLAGSHIP_TUNED: what the
    # cells flagship-train-solo and flagship-train-dp4 run
    "flagship": flagship_model_config,
    "tiny": tiny_model_config,                # CPU smoke shape
    # DALL-E-XL ~3B for pod-slice peers: the cell xl-train-solo
    "xl": xl_model_config,
    # SmallThinker-21BA3B-Instruct cut to one chip's share of a layer
    # (a dataclass of its own): the cell smallthinker21b-train-solo
    "smallthinker21b": smallthinker21b_model_config,
    # Trinity-Mini cut to one of 16 chips' share of a layer (a subclass
    # that states its mechanisms as fields): trinitymini-train-solo
    "trinitymini": trinitymini_model_config,
    # JoyAI-LLM-Flash cut to one of 32 chips' share of a layer (latent
    # attention, a prediction module): joyaiflash-train-solo
    "joyaiflash": joyaiflash_model_config,
    # LFM2-8B-A1B cut to one of 4 chips' share of a layer (a gated short
    # convolution in four layers of five, 64-wide heads, a tied head):
    # lfm2moe-train-solo
    "lfm2moe": lfm2moe_model_config,
    # Keye-VL-2.0-30B-A3B's language model cut to one of 16 chips' share
    # of a layer (an indexer chooses 2 048 keys a query, attention over
    # the chosen keys, three position rows): keyevl2-train-solo
    "keyevl2": keyevl2_model_config,
    # the 52-layer stack of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 cut
    # to one of 16 chips' share of a layer (Mamba-2 mixers and their
    # chunked scan, layers of one part, two-product relu^2 experts):
    # twotower30b-train-solo
    "twotower30b": twotower30b_model_config,
    # Qwen3-Next-80B-A3B-Instruct cut to one of 32 chips' share of a layer
    # (gated-delta-rule mixers in three layers of four, gated attention on
    # 256-wide heads with a quarter of each rotated, 16 of 512 experts at
    # ten a token beside a gated shared expert): qwen3next80b-train-solo
    "qwen3next80b": qwen3next80b_model_config,
    # Ouro-2.6B cut to 6 of its 48 layers and half its vocabulary (a dense
    # stack of four-norm layers run four times on one set of parameters, an
    # exit gate and the head after every pass): ouro2b6-train-solo
    "ouro2b6": ouro2b6_model_config,
}

CONFIG_CLASSES = (ModelConfig, OptimizerConfig, TrainerConfig, CollabConfig,
                  PeerConfig)
# Every architecture's configuration class. A preset builds one of them;
# a field two of them share (vocab_text, dtype, ...) is one flag.
MODEL_CLASSES = (ModelConfig, SparseLMConfig, AfmoeLMConfig,
                 JoyAILMConfig, Lfm2MoeLMConfig, KeyeLMConfig,
                 NemotronHLMConfig, Qwen3NextLMConfig, OuroLMConfig)


def maybe_wandb_run(project: Optional[str], name: str):
    """Best-effort wandb run, mirroring the aux-peer sink (reference
    run_aux_peer.py:92-93): None when no project is configured or wandb
    is unusable — the JSONL metrics file stays the always-on sink, and a
    missing install / auth failure / dead network must never take a
    training peer down."""
    if not project:
        return None
    try:
        import wandb
        return wandb.init(project=project, name=name)
    except Exception:  # noqa: BLE001 - wandb is strictly optional
        logger.warning("wandb unavailable (--wandb-project %s); "
                       "continuing with the metrics file", project,
                       exc_info=True)
        return None


def make_epoch_sink(metrics_file: Optional[str], wandb_run,
                    timings_fn=None):
    """Per-epoch report sink: one JSON line per epoch to
    ``metrics_file`` and, when a wandb run is live, the same scalars
    (timings flattened under ``timings/``) to wandb."""
    def on_epoch(report):
        timings = timings_fn() if timings_fn is not None else {}
        row = {
            "epoch": report.epoch,
            "loss": report.loss,
            "mini_steps": report.mini_steps,
            "samples_per_second": report.samples_per_second,
            "timings": timings,
        }
        if metrics_file:
            with open(metrics_file, "a") as f:
                f.write(json.dumps(row) + "\n")
        if wandb_run is not None:
            scalars = {k: v for k, v in row.items()
                       if k != "timings" and v is not None}
            scalars.update({f"timings/{k}": v
                            for k, v in (timings or {}).items()})
            wandb_run.log(scalars)
    return on_epoch


def add_model_args(parser: argparse.ArgumentParser) -> None:
    """One flag a field of every model class, a shared field once; none
    for a field its class lists in ``no_flag``."""
    seen: set = set()
    for cls in MODEL_CLASSES:
        check_no_collisions(cls, *CONFIG_CLASSES[1:])
        add_dataclass_args(parser, cls, skip=(
            *seen, *getattr(cls, "no_flag", ())))
        seen |= {f.name for f in dataclasses.fields(cls)}


def model_from_args(args: argparse.Namespace):
    """The preset's configuration, of whichever class the preset builds,
    with the field flags the user passed laid over it."""
    base = MODEL_PRESETS[args.preset]()
    return dataclass_from_args(type(base), args, base=base)


def decodable_model_from_args(args: argparse.Namespace, prog: str):
    """:func:`model_from_args` for the entry points that decode, serve or
    rebuild the DALL-E's gradient layout: a preset whose architecture the
    decode path cannot run is refused here, at start, in one sentence."""
    model = model_from_args(args)
    if model.decode_missing:
        raise SystemExit(
            f"{prog}: preset {args.preset!r} trains (run_trainer) but "
            f"cannot be decoded, served or assisted yet: "
            f"{model.decode_missing}.")
    return model


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dalle-tpu-trainer", description=__doc__.splitlines()[0])
    parser.add_argument("--preset", choices=sorted(MODEL_PRESETS),
                        default="flagship",
                        help="base model shape that field flags override")
    parser.add_argument("--wandb-project", type=str, default=None,
                        help="log per-epoch training stats to this wandb "
                             "project (mirrors the aux peer's swarm-wide "
                             "sink; requires wandb to be installed)")
    parser.add_argument("--max-epochs", type=int, default=None,
                        help="stop after this many global steps")
    parser.add_argument("--max-steps", type=int, default=None,
                        help="stop after this many local mini-steps")
    parser.add_argument("--warmup-batches", type=int, default=3,
                        help="compile/self-check steps before joining")
    parser.add_argument("--data-path", type=str, default=None,
                        help="codes dataset dir/file (default: synthetic)")
    parser.add_argument("--tokenizer-path", type=str, default=None,
                        help="tokenizer.json for --data-path captions")
    parser.add_argument("--metrics-file", type=str, default=None,
                        help="append one JSON line per epoch to this file")
    parser.add_argument("--checkpoint-dir", type=str, default=None,
                        help="resume from + checkpoint into this directory")
    parser.add_argument("--save-every-epochs", type=int, default=10)
    parser.add_argument("--backup-every-epochs", type=int, default=1)
    parser.add_argument("--keep-checkpoints", type=int, default=3)
    parser.add_argument("--platform", type=str, default=None,
                        help="force a jax platform (cpu/tpu) before init")
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="capture a JAX profiler trace of a few early "
                             "steps into this directory")
    parser.add_argument("--log-level", type=str, default="INFO")
    add_model_args(parser)
    for cls in CONFIG_CLASSES[1:]:
        add_dataclass_args(parser, cls)
    return parser


def configs_from_args(args: argparse.Namespace):
    return (model_from_args(args),
            dataclass_from_args(OptimizerConfig, args),
            dataclass_from_args(TrainerConfig, args),
            dataclass_from_args(CollabConfig, args),
            dataclass_from_args(PeerConfig, args))


def banner(task) -> None:
    """Connection banner with the copyable joiner line (utils.py:39-56)."""
    if not task.slice_role.swarm_enabled:
        return  # followers of a multi-host slice have no DHT to advertise
    addr = task.dht.visible_address
    logger.info("=" * 60)
    logger.info("peer %s listening on %s", task.dht.peer_id[:16], addr)
    logger.info("to join this swarm, run a peer with:")
    logger.info("    --initial-peers %s", addr)
    logger.info("=" * 60)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=args.log_level,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.loop import train_loop

    model, opt, trainer, collab, peer = configs_from_args(args)
    task = TrainingTask(model, opt, trainer, collab, peer,
                        data_path=args.data_path,
                        tokenizer_path=args.tokenizer_path)

    wandb_run = maybe_wandb_run(args.wandb_project,
                                f"trainer-{peer.experiment_prefix}")
    on_epoch = make_epoch_sink(
        args.metrics_file, wandb_run,
        timings_fn=lambda: task.collab_optimizer.last_timings)

    try:
        with task:
            banner(task)
            reports = train_loop(task,
                                 max_epochs=args.max_epochs,
                                 max_steps=args.max_steps,
                                 warmup_steps=args.warmup_batches,
                                 on_epoch=on_epoch,
                                 checkpoint_dir=args.checkpoint_dir,
                                 save_every=args.save_every_epochs,
                                 backup_every=args.backup_every_epochs,
                                 keep_checkpoints=args.keep_checkpoints,
                                 profile_dir=args.profile_dir)
    finally:
        # flush wandb even when the loop exits via KeyboardInterrupt /
        # a DHT exception — same shutdown contract as the aux peer
        if wandb_run is not None:
            wandb_run.finish()
    if reports:
        logger.info("done: %d epochs, final mean loss %.4f",
                    len(reports), reports[-1].loss)
    return 0


if __name__ == "__main__":
    sys.exit(main())
