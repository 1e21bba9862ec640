"""What the ``trinitymini`` tolerance is set from: the comparison
``harness.reference_check`` makes (the real jitted grad step on the tiled
pair of check sequences against the yardstick's float32 reference), for the
program as it ships and for the reference's own equations computed in
lower precision; how many of the tokens' top-k expert sets differ between
the program's bfloat16 path and the reference; and the same comparisons
with the reference evaluated **at the sets the program chose**
(``loss_and_grads_at``), where near-ties drop out and rounding is left.
After ``probes/smallthinker_precision.py``, whose float8 operands and
error table it uses.

    python3 -m benchmark.probes.trinity_precision --seed <n> [--out <dir>]

Prints one JSON line a reading (``--out``: also, with every leaf's
distance, to ``<dir>/precision.jsonl``). ``grad_rel_l2_max`` /
``loss_rel_err`` are what ``correct`` reads:

- ``as_shipped``: bfloat16 activations from float32 parameters, float32
  router product and sigmoid (the cell's own reading);
- ``reference_fp8_operands``: the yardstick's equations in float32 with
  both operands of every product rounded to float8_e4m3fn (scaled a
  tensor, straight-through backward): the nearest precision below
  bfloat16 activations;
- ``reference_in_bf16``: the yardstick's equations computed in bfloat16
  throughout at the default matmul precision, against itself in float32;
- ``chosen_experts``: of the expert layers x tokens top-k sets, how many
  differ between the program and the reference;
- ``memory``: the device allocator's counters after each stage.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.probes.smallthinker_precision import _errors, _Fp8Operands


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="trinitymini-train-solo")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=None)
    parser.add_argument("--root", default=None,
                        help="development: another manifest root (a tiny "
                             "rehearsal root on the CPU)")
    args = parser.parse_args(argv)

    from benchmark import harness
    from benchmark.manifest import Manifest
    from dalle_tpu.cli import run_trainer
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

    grads, metrics = task.grad_step(params, batch)
    shipped = float(metrics["loss"]), host(grads)
    del grads
    memory("the system's grad step")
    ref_loss, ref_grads = y.loss_and_grads(params, text2, image2, model,
                                           checkpoint_blocks=True)
    ref_loss, ref_grads = float(ref_loss), host(ref_grads)
    memory("the reference")

    # the sets the program chose (every expert layer sows them)
    _, kept = jax.jit(lambda p: task.model.apply(
        p, text2, image2, mutable=["intermediates"]))(params)
    ours = np.stack([np.asarray(layer["chosen"][0]) for _, layer in sorted(
        kept["intermediates"].items(), key=lambda kv: int(kv[0][6:]))])
    del kept
    theirs = np.asarray(y.chosen_experts(params, text2, image2, model))
    differ = np.any(np.sort(ours, -1) != np.sort(theirs, -1), axis=-1)
    say("chosen_experts", sets=int(differ.size),
        sets_that_differ=int(differ.sum()),
        by_layer=differ.sum(axis=(1, 2)).tolist())
    at_loss, at_grads = y.loss_and_grads_at(ours, params, text2, image2,
                                            model, checkpoint_blocks=True)
    at_ours = float(at_loss), host(at_grads)
    del at_grads
    reading = _errors(*shipped, ref_loss, ref_grads)
    say("as_shipped", inside_the_bounds=inside(reading), **reading,
        at_its_sets=_errors(*shipped, *at_ours))
    del shipped

    def in_lower_precision(params, chosen):
        return jax.value_and_grad(lambda q: y.loss_fn(
            q, text2, image2, model, True, chosen)[0])(params)

    # the reference with fp8 operands, float32 otherwise, its own sets
    plain, y.jnp = y.jnp, _Fp8Operands()
    try:
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.jit(lambda p: in_lower_precision(p, None))(
                params)
        reading = _errors(float(loss), host(grads), ref_loss, ref_grads)
    finally:
        y.jnp = plain
    del loss, grads
    memory("the reference with float8 operands")
    say("reference_fp8_operands", inside_the_bounds=inside(reading),
        **reading)

    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    # bfloat16 throughout, at its own sets and at the program's
    loss, grads = jax.jit(lambda p: in_lower_precision(p, None))(half)
    reading = _errors(float(loss), host(grads), ref_loss, ref_grads)
    loss, grads = jax.jit(in_lower_precision)(half, jnp.asarray(ours))
    say("reference_in_bf16", inside_the_bounds=inside(reading), **reading,
        at_its_sets=_errors(float(loss), host(grads), *at_ours))


if __name__ == "__main__":
    main()
