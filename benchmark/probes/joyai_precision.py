"""What the ``joyaiflash`` tolerance is set from, and why the routed
leaves read a tenth and more: the comparison ``harness.reference_check``
makes (the real jitted grad step on the tiled pair of check sequences
against the yardstick's float32 reference), for the program as it ships,
for the program with each layer's top-k **taken again** in the backward
pass's replay (what it did before PR 44 kept the chosen sets across the
rematerialisation), and for the reference's own equations computed in
lower precision; how many of the tokens' top-k expert sets differ between
the program's bfloat16 path and the reference; and the program against
the reference evaluated **at the sets the program chose**
(``loss_and_grads_at``), where near-ties drop out and rounding is what is
left. After ``probes/trinity_precision.py`` (``probes/
smallthinker_precision.py`` has the float8 operands and the error table);
the prediction module's block is the last expert layer of the sets.

    python3 -m benchmark.probes.joyai_precision --seed <n> [--out <dir>]
        [--readings as_shipped,control,...]

Prints one JSON line a reading (``--out``: also, with every leaf's
distance, to ``<dir>/precision.jsonl``):

- ``as_shipped``: the task's grad step (``at_its_sets``: against the
  reference at the sets of ``one_sequence_a_program``;
  ``to_one_sequence_a_program``: its distance to that program's gradients);
- ``one_sequence_a_program``: one jitted forward-and-backward a check
  sequence (the micro-batch the grad step scans) that also returns the
  sets every expert layer chose, which a rematerialised layer keeps
  (``sparse_lm.KEPT_OF_A_LAYER``), so they are the sets it differentiates;
  ``at_its_sets`` is the comparison the harness does not make (it hands
  the yardstick no sets). With it ``chosen_experts`` (of the layers x
  tokens top-k sets, how many differ from the reference's) and
  ``reference_norms`` (the L2 norm of the reference's gradient by kind of
  leaf: no distance is a quotient by next to nothing);
- ``sets_taken_again``: the same program built with ``KEPT_OF_A_LAYER``
  less ``"chosen"`` (before PR 44), against the reference at the sets its
  forward pass returned;
- ``control``: the yardstick's equations in float32 with both operands of
  every product rounded to float8_e4m3fn, the nearest precision below
  bfloat16 activations that one step's gradients can tell, **standing
  where the task stands in** ``harness.reference_check``: its ``correct``
  is the harness's own verdict and has to be false;
- ``reference_in_bf16``: the yardstick's equations in bfloat16 throughout;
- ``memory``: the device allocator's counters after each stage.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.probes.smallthinker_precision import _errors, _Fp8Operands


READINGS = ("as_shipped", "sets_taken_again", "control",
            "reference_in_bf16")


class _InItsPlace:
    """Stands where the task stands in ``harness.reference_check``: its
    ``grad_step`` is ``step``, the rest is the task's."""

    def __init__(self, task, step):
        self.train_state, self.mesh = task.train_state, task.mesh
        self.local_batch_size = task.local_batch_size
        self.grad_step = step


def float8_control(y, model):
    """``step(params, batch) -> (grads, {"loss": loss})`` on the pair of
    sequences that ``batch`` tiles: the yardstick's equations, float32,
    both operands of every product rounded to float8_e4m3fn first."""
    def step(params, batch):
        text2, image2 = batch["text"][:2], batch["image"][:2]
        plain, y.jnp = y.jnp, _Fp8Operands()
        try:
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.jit(jax.value_and_grad(
                    lambda q: y.loss_fn(q, text2, image2, model, True)[0]))(
                        params)
        finally:
            y.jnp = plain
        return grads, {"loss": loss}
    return step


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="joyaiflash-train-solo")
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
    from dalle_tpu.parallel.mesh import batch_sharding
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = (Manifest(args.root) if args.root else Manifest()).cell(
        args.workload)
    model, tol = cell.config["model"], cell.config["tolerance"]
    y = cell.yardstick
    device = jax.devices()[0]
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

    def memory(stage):
        stats = device.memory_stats() or {}
        say("memory", stage=stage, **{key: stats.get(key) for key in (
            "bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved")})

    def inside(reading):
        return (reading["loss_rel_err"] <= tol["loss_rel"]
                and reading["grad_rel_l2_max"] <= tol["grad_rel_l2"])

    host = lambda tree: jax.tree.map(np.asarray, tree)

    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, args.seed))))
    params = task.train_state.params
    jax.block_until_ready(task.train_state)
    rng = np.random.default_rng(args.seed % harness.SEED_MODULUS)
    text2 = rng.integers(2, model["vocab_text"],
                         (2, model["text_seq_len"]), dtype=np.int32)
    image2 = rng.integers(0, model["vocab_image"],
                          (2, model["image_grid"] ** 2), dtype=np.int32)
    n = task.local_batch_size
    batch = jax.device_put(
        {"text": np.tile(text2, (n // 2, 1)),
         "image": np.tile(image2, (n // 2, 1))}, batch_sharding(task.mesh))
    text2, image2 = jnp.asarray(text2), jnp.asarray(image2)

    def system(step):
        grads, metrics = step(params, batch)
        return float(metrics["loss"]), host(grads)

    ref_loss, ref_grads = y.loss_and_grads(params, text2, image2, model,
                                           checkpoint_blocks=True)
    ref_loss, ref_grads = float(ref_loss), host(ref_grads)
    memory("the reference")

    def one_sequence_a_program():
        """Loss, gradients and the chosen sets (expert layers, 2, T, k; the
        prediction module's block last) of the pair, from ONE
        forward-and-backward program a sequence, the micro-batch the grad
        step scans: the sets come out of the program that differentiates
        them. (A forward-only program's are other sets from the second
        expert layer on: it rounds the residual stream otherwise.)"""
        module = sparse_lm.build(task.model_cfg, task.mesh)

        def loss_and_sets(p, text, image):
            (loss, _), sown = module.apply(p, text, image,
                                           mutable=["intermediates"])
            return loss, sown["intermediates"]
        step = jax.jit(jax.value_and_grad(loss_and_sets, has_aux=True))
        losses, grads, chosen = [], None, []
        for i in range(2):
            (loss, sown), g = step(params, text2[i:i + 1], image2[i:i + 1])
            layers = [sown[f"layer_{j}"]
                      for j in range(model["num_hidden_layers"])
                      if f"layer_{j}" in sown]
            if "mtp" in sown:
                layers.append(sown["mtp"]["block"])
            chosen.append(np.stack([np.asarray(layer["chosen"][0])
                                    for layer in layers]))
            losses.append(float(loss))
            g = host(g)
            grads = g if grads is None else jax.tree.map(
                lambda a, b: (a + b) / 2, grads, g)
        return sum(losses) / 2, grads, np.concatenate(chosen, axis=1)

    def compared(loss, grads, at):
        """Against the reference's own sets (what ``correct`` reads), and
        against the reference at the sets ``at``'s program chose."""
        reading = _errors(loss, grads, ref_loss, ref_grads)
        return {"inside_the_bounds": inside(reading), **reading,
                "at_its_sets": _errors(loss, grads, *at)}

    def reference_at(chosen):
        loss, grads = y.loss_and_grads_at(chosen, params, text2, image2,
                                          model, checkpoint_blocks=True)
        return float(loss), host(grads)

    if "as_shipped" in readings:
        loss, grads, ours = one_sequence_a_program()
        theirs = np.asarray(y.chosen_experts(params, text2, image2, model))
        differ = np.any(np.sort(ours, -1) != np.sort(theirs, -1), axis=-1)
        say("chosen_experts", sets=int(differ.size),
            sets_that_differ=int(differ.sum()),
            by_layer=differ.sum(axis=(1, 2)).tolist())
        norms = {jax.tree_util.keystr(k): float(np.linalg.norm(g))
                 for k, g in jax.tree_util.tree_flatten_with_path(
                     ref_grads)[0]}
        kinds = {kind: [v for k, v in norms.items() if kind in k]
                 for kind in ("['router']", "['experts']", "['shared']",
                              "['attn']")}
        say("reference_norms", by_leaf=norms, **{
            kind.strip("[']"): [min(v), max(v)] for kind, v in kinds.items()})
        at_ours = reference_at(ours)
        say("one_sequence_a_program", **compared(loss, grads, at_ours))
        shipped = system(task.grad_step)
        say("as_shipped", **compared(*shipped, at_ours),
            to_one_sequence_a_program=_errors(*shipped, loss, grads))
        del shipped, grads
        memory("the system's grad step")

    if "sets_taken_again" in readings:
        kept = sparse_lm.KEPT_OF_A_LAYER
        sparse_lm.KEPT_OF_A_LAYER = tuple(k for k in kept if k != "chosen")
        try:
            loss, grads, forward = one_sequence_a_program()
        finally:
            sparse_lm.KEPT_OF_A_LAYER = kept
        say("sets_taken_again", **compared(loss, grads,
                                           reference_at(forward)))
        del grads

    if "control" in readings:
        grads, metrics = float8_control(y, model)(params, batch)
        held = lambda *_: (grads, metrics)
        reading = _errors(float(metrics["loss"]), host(grads), ref_loss,
                          ref_grads)
        verdict = harness.reference_check(_InItsPlace(task, held), cell,
                                          args.seed)
        assert verdict["grad_rel_l2_max"] == reading["grad_rel_l2_max"]
        say("control", correct=verdict["ok"],
            inside_the_bounds=inside(reading), **reading)
        del grads, metrics, held, verdict
        memory("the reference with float8 operands")

    if "reference_in_bf16" in readings:
        half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        loss, grads = jax.jit(jax.value_and_grad(lambda q: y.loss_fn(
            q, text2, image2, model, True)[0]))(half)
        reading = _errors(float(loss), host(grads), ref_loss, ref_grads)
        say("reference_in_bf16", inside_the_bounds=inside(reading),
            **reading)


if __name__ == "__main__":
    main()
