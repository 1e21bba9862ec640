"""What the ``keyevl2`` tolerance is set from: the comparison
``harness.reference_check`` makes (the real jitted grad step on the tiled
pair of check sequences against the yardstick's float32 reference), for
the program as it ships and for the reference's own equations computed in
lower precision; and how many of the (layer, query) selected key sets
differ between the program's bfloat16 path and the reference. After
``probes/lfm2_precision.py``; the float8 control and the stand-in are
``probes/joyai_precision.py``'s.

    python3 -m benchmark.probes.keye_precision --seed <n> [--out <dir>]
        [--readings as_shipped,chosen_keys,control]

Prints one JSON line a reading (``--out``: also, with every leaf's
distance, to ``<dir>/precision.jsonl``):

- ``as_shipped``: ``harness.reference_check`` on the task itself, the
  cell's own comparison (``loss_rel_err`` / ``grad_rel_l2_max`` are what
  ``correct`` reads), with the largest distance by kind of leaf (the
  indexer, attention, the routers, the routed experts, embedding and head);
- ``chosen_keys``: the program's sets (its own scores' kernel and
  selection on the indexer's operands as its forward pass makes them, a
  check sequence at a time) against the reference's
  (``yardstick.chosen_keys``): of the layers x queries sets, how many
  differ, and in those that do, how many of a set's keys;
- ``control``: the yardstick's equations in float32 with both operands of
  every product rounded to float8_e4m3fn, **standing where the task stands
  in** ``harness.reference_check``: its ``correct`` is the harness's own
  verdict and has to be false.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.probes.joyai_precision import _InItsPlace, float8_control
from benchmark.probes.smallthinker_precision import _errors

READINGS = ("as_shipped", "chosen_keys", "control")
KINDS = ("['indexer']", "['attn']", "['router']", "['experts']",
         "['token_emb']", "['lm_head']")


def by_kind(errs):
    """The largest distance among the leaves of each kind (``attn``: the
    indexer's leaves apart)."""
    of = lambda kind: [v for k, v in errs.items() if kind in k and (
        kind != "['attn']" or "['indexer']" not in k)]
    return {kind.strip("[']"): max(of(kind)) for kind in KINDS if of(kind)}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="keyevl2-train-solo")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=None)
    parser.add_argument("--readings", default=",".join(READINGS),
                        help="which of " + ", ".join(READINGS))
    parser.add_argument("--root", default=None,
                        help="development: another manifest root (a tiny "
                             "rehearsal root on the CPU)")
    args = parser.parse_args(argv)
    readings = args.readings.split(",")
    if not set(readings) <= set(READINGS):
        parser.error(f"--readings: {args.readings!r} names none of "
                     + ", ".join(READINGS))

    from benchmark import harness
    from benchmark.manifest import Manifest
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = (Manifest(args.root) if args.root else Manifest()).cell(
        args.workload)
    model, tol = cell.config["model"], cell.config["tolerance"]
    y = cell.yardstick
    log = None
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        log = open(Path(args.out) / "precision.jsonl", "a")

    def say(name, **reading):
        line = {"reading": name, "seed": args.seed, **reading}
        if log:
            log.write(json.dumps(line) + "\n")
            log.flush()
        short = lambda v: ({k: short(w) for k, w in v.items()
                            if k != "by_leaf"} if isinstance(v, dict) else v)
        print(json.dumps(short(line)), flush=True)

    def inside(reading):
        return (reading["loss_rel_err"] <= tol["loss_rel"]
                and reading["grad_rel_l2_max"] <= tol["grad_rel_l2"])

    host = lambda tree: jax.tree.map(np.asarray, tree)

    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, args.seed))))
    params = task.train_state.params
    jax.block_until_ready(task.train_state)

    # the reference once, for every reading that stands in the harness's
    # comparison
    once = {}
    plain = y.loss_and_grads
    y.loss_and_grads = lambda *a, **kw: (
        once.get("it") or once.setdefault("it", plain(*a, **kw)))

    def checked(stand_in):
        """``harness.reference_check``'s verdict with every leaf's
        distance beside it."""
        kept = {}
        step = stand_in.grad_step

        def keeping(p, batch):
            kept["out"] = step(p, batch)
            return kept["out"]
        verdict = harness.reference_check(
            _InItsPlace(stand_in, keeping), cell, args.seed)
        verdict.pop("first_batch")
        grads, metrics = kept["out"]
        ref_loss, ref_grads = once["it"]
        reading = _errors(float(metrics["loss"]), host(grads),
                          float(ref_loss), host(ref_grads))
        assert reading["grad_rel_l2_max"] == verdict["grad_rel_l2_max"]
        return {"correct": verdict["ok"], "loss": verdict["loss"],
                "inside_the_bounds": inside(reading),
                "by_kind": by_kind(reading["by_leaf"]), **reading}

    # the pair of check sequences, drawn as the harness draws them
    rng = np.random.default_rng(args.seed % harness.SEED_MODULUS)
    text2 = jnp.asarray(rng.integers(
        2, model["vocab_text"], (2, model["text_seq_len"]), dtype=np.int32))
    image2 = jnp.asarray(rng.integers(
        0, model["vocab_image"], (2, model["image_grid"] ** 2),
        dtype=np.int32))

    if "as_shipped" in readings:
        say("as_shipped", **checked(task))

    if "chosen_keys" in readings:
        cfg = task.model_cfg
        module = sparse_lm.build(cfg, task.mesh)
        scale = (cfg.index_heads * cfg.index_head_dim) ** -0.5

        @jax.jit
        def operands(p, text, image):
            _, kept = module.apply(
                p, text, image, mutable=["intermediates"],
                capture_intermediates=lambda m, _: isinstance(
                    m, sparse_lm.Indexer))
            return [kept["intermediates"][f"layer_{i}"]["attn"]["indexer"][
                "__call__"][0] for i in range(cfg.num_hidden_layers)]

        @jax.jit
        def its_sets(qi, ki, w):
            t = qi.shape[1]
            if lowering.mosaic():
                scores = sparse_lm.index_kernels.index_scores(
                    qi, ki, w, scale, interpret=lowering.interpret())
            else:
                scores = sparse_lm.dense_index_scores(qi, ki, w, scale)
            return sparse_lm.select_keys(
                scores[:, :t, :t], cfg.index_topk,
                cfg.index_chunk) > sparse_lm.OFF

        differ = flipped = 0
        by_layer = [0] * cfg.num_hidden_layers
        for b in range(2):
            theirs = y.chosen_keys(params, text2[b:b + 1], image2[b:b + 1],
                                   model)
            for i, layer in enumerate(operands(
                    params, text2[b:b + 1], image2[b:b + 1])):
                apart = np.asarray(jnp.sum(its_sets(*layer) != theirs[i],
                                           axis=-1))
                by_layer[i] += int((apart > 0).sum())
                differ += int((apart > 0).sum())
                flipped += int(apart.sum())
            del theirs
        sets = 2 * cfg.num_hidden_layers * (text2.shape[1] + image2.shape[1])
        say("chosen_keys", sets=sets, sets_that_differ=differ,
            by_layer=by_layer,
            keys_apart_in_a_set_that_differs=flipped / max(differ, 1))

    if "control" in readings:
        say("control", **checked(_InItsPlace(
            task, float8_control(y, model))))


if __name__ == "__main__":
    main()
