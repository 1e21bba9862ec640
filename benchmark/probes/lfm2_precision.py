"""What the ``lfm2moe`` tolerance is set from: the comparison
``harness.reference_check`` makes (the real jitted grad step on the tiled
pair of check sequences against the yardstick's float32 reference), for
the program as it ships and for the reference's own equations computed in
lower precision; how many of the tokens' top-4 expert sets differ between
the program's bfloat16 path and the reference; and the program against the
reference evaluated **at the sets the program chose**
(``loss_and_grads_at``), where near-ties drop out and rounding is what is
left. After ``probes/joyai_precision.py``, whose float8 control and
stand-in it uses.

    python3 -m benchmark.probes.lfm2_precision --seed <n> [--out <dir>]
        [--readings as_shipped,at_its_sets,control,reference_in_bf16]

Prints one JSON line a reading (``--out``: also, with every leaf's
distance, to ``<dir>/precision.jsonl``):

- ``as_shipped``: ``harness.reference_check`` on the task itself, the
  cell's own comparison (``loss_rel_err`` / ``grad_rel_l2_max`` are what
  ``correct`` reads), with the largest distance by kind of leaf (the
  convolutions' taps and projections, attention, the routers, the routed
  experts, the dense block, the tied table);
- ``at_its_sets``: one jitted forward-and-backward a check sequence (the
  micro-batch the grad step scans) that also returns the sets every expert
  layer chose (kept across the rematerialisation, so the ones it
  differentiates), against the reference at those sets; with it
  ``chosen_experts``: of the expert layers x tokens top-4 sets, how many
  differ from the reference's own;
- ``control``: the yardstick's equations in float32 with both operands of
  every product rounded to float8_e4m3fn, the nearest precision below
  bfloat16 activations that one step's gradients can tell, **standing
  where the task stands in** ``harness.reference_check``: its ``correct``
  is the harness's own verdict and has to be false;
- ``reference_in_bf16``: the yardstick's equations in bfloat16 throughout.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.probes.joyai_precision import _InItsPlace, float8_control
from benchmark.probes.smallthinker_precision import _errors

READINGS = ("as_shipped", "at_its_sets", "control", "reference_in_bf16")
KINDS = ("['taps']", "['conv']", "['attn']", "['router']", "['experts']",
         "['dense']", "['token_emb']")


def by_kind(errs):
    """The largest distance among the leaves of each kind."""
    return {kind.strip("[']"): max(v for k, v in errs.items() if kind in k)
            for kind in KINDS if any(kind in k for k in errs)}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="lfm2moe-train-solo")
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
    y.loss_and_grads(params, text2, image2, model, checkpoint_blocks=True)

    if "as_shipped" in readings:
        say("as_shipped", **checked(task))

    if "at_its_sets" in readings:
        module = sparse_lm.build(task.model_cfg, task.mesh)

        def loss_and_sets(p, text, image):
            (loss, _), sown = module.apply(p, text, image,
                                           mutable=["intermediates"])
            return loss, sown["intermediates"]
        step = jax.jit(jax.value_and_grad(loss_and_sets, has_aux=True))
        losses, grads, chosen = [], None, []
        for i in range(2):
            (loss, sown), g = step(params, text2[i:i + 1], image2[i:i + 1])
            chosen.append(np.stack([
                np.asarray(sown[f"layer_{j}"]["chosen"][0])
                for j in range(model["num_hidden_layers"])
                if f"layer_{j}" in sown]))
            losses.append(float(loss))
            g = host(g)
            grads = g if grads is None else jax.tree.map(
                lambda a, b: (a + b) / 2, grads, g)
        ours = np.concatenate(chosen, axis=1)
        theirs = np.asarray(y.chosen_experts(params, text2, image2, model))
        differ = np.any(np.sort(ours, -1) != np.sort(theirs, -1), axis=-1)
        say("chosen_experts", sets=int(differ.size),
            sets_that_differ=int(differ.sum()),
            by_layer=differ.sum(axis=(1, 2)).tolist())
        at_loss, at_grads = y.loss_and_grads_at(
            ours, params, text2, image2, model, checkpoint_blocks=True)
        reading = _errors(sum(losses) / 2, grads, float(at_loss),
                          host(at_grads))
        say("at_its_sets", by_kind=by_kind(reading["by_leaf"]), **reading)
        del grads, at_grads

    if "control" in readings:
        say("control", **checked(_InItsPlace(
            task, float8_control(y, model))))

    if "reference_in_bf16" in readings:
        half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        loss, grads = jax.jit(jax.value_and_grad(lambda q: y.loss_fn(
            q, text2, image2, model, True)[0]))(half)
        ref_loss, ref_grads = once["it"]
        reading = _errors(float(loss), host(grads), float(ref_loss),
                          host(ref_grads))
        say("reference_in_bf16", inside_the_bounds=inside(reading),
            by_kind=by_kind(reading["by_leaf"]), **reading)


if __name__ == "__main__":
    main()
