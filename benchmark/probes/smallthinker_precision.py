"""What the ``smallthinker21b`` tolerance is set from: the same comparison
``harness.reference_check`` makes (the real jitted grad step on the tiled
pair of check sequences against the yardstick's float32 reference), made
for the program as it ships and for computations in lower precision; how
many of the tokens' top-k expert sets differ between the program's
bfloat16 path and the reference; and the same comparisons with the
reference evaluated **at the sets the program chose**
(``loss_and_grads_at``), where near-ties drop out and rounding is what is
left.

    python3 -m benchmark.probes.smallthinker_precision --seed <n> [--out <dir>]

Prints one JSON line a reading (``--out``: also, with every leaf's
distance, to ``<dir>/precision.jsonl``). ``grad_rel_l2_max`` / ``loss_rel_err``
are what ``correct`` reads; ``at_its_sets`` is the comparison the harness
does not make (it hands the yardstick no sets):

- ``as_shipped``: bfloat16 activations from float32 parameters, float32
  router product (the cell's own reading);
- ``bf16_params``: the same grad step on a parameter tree rounded to
  bfloat16 (the dtype check of ``correct`` refuses it outright: this is
  what the bounds alone say);
- ``bf16_router``: the router's product taken in bfloat16 (a patched
  ``ExpertLayer.route``; nothing ships with it);
- ``reference_in_bf16``: the yardstick's own equations computed in
  bfloat16 at the default matmul precision, against itself in float32;
- ``reference_fp8_operands``: the yardstick's equations in float32 with
  both operands of every product rounded to float8_e4m3fn (scaled a
  tensor to its largest magnitude, straight-through backward): the
  nearest precision below bfloat16 activations;
- ``chosen_experts``: of the layers x tokens top-k sets, how many differ;
- ``memory``: the device allocator's counters after each stage.
"""
import argparse
import json
import statistics
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _errors(loss, grads, ref_loss, ref_grads):
    def rel_l2(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    errs = {jax.tree_util.keystr(k): rel_l2(g, r) for (k, g), r in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(ref_grads))}
    worst = max(errs, key=errs.get)
    return {"loss_rel_err": abs(float(loss) - float(ref_loss))
            / abs(float(ref_loss)),
            "grad_rel_l2_max": errs[worst], "worst_leaf": worst,
            "grad_rel_l2_median": statistics.median(errs.values()),
            "by_leaf": errs}


class _Fp8Operands:
    """``jax.numpy`` with both operands of ``dot`` and ``einsum`` rounded
    to float8_e4m3fn first (per-tensor scale, straight-through)."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def _round(a):
        scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
        r = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
        return a + jax.lax.stop_gradient(r - a)

    def dot(self, a, b):
        return jnp.dot(self._round(a), self._round(b))

    def einsum(self, spec, *operands):
        return jnp.einsum(spec, *map(self._round, operands))


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="smallthinker21b-train-solo")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=None)
    parser.add_argument("--root", default=None,
                        help="development: another manifest root (a tiny "
                             "rehearsal root on the CPU)")
    args = parser.parse_args(argv)

    from benchmark import harness
    from benchmark.manifest import Manifest
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.parallel.mesh import batch_sharding
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.training.steps import make_grad_step
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
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved", "largest_alloc_size")})

    def inside(reading):
        return (reading["loss_rel_err"] <= tol["loss_rel"]
                and reading["grad_rel_l2_max"] <= tol["grad_rel_l2"])

    host = lambda tree: jax.tree.map(np.asarray, tree)

    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, args.seed))))
    params = task.train_state.params
    jax.block_until_ready(task.train_state)
    memory("train state")
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

    def system(step, params):
        grads, metrics = step(params, batch)
        return float(metrics["loss"]), host(grads)

    def sets_of(module, params):
        """(layers, 2, T, k): the sets ``module`` chooses on the pair."""
        _, kept = jax.jit(lambda p: module.apply(
            p, text2, image2, mutable=["intermediates"]))(params)
        return np.stack([np.asarray(
            kept["intermediates"][f"layer_{i}"]["chosen"][0])
            for i in range(model["num_hidden_layers"])])

    def reference_at(chosen):
        loss, grads = y.loss_and_grads_at(chosen, params, text2, image2,
                                          model, checkpoint_blocks=True)
        return float(loss), host(grads)

    def compared(loss, grads, chosen):
        """Against the reference's own sets (what ``correct`` reads), and
        against the reference at ``chosen``."""
        reading = _errors(loss, grads, ref_loss, ref_grads)
        at_loss, at_grads = (at_shipped if chosen is ours
                             else reference_at(chosen))
        return {"inside_the_bounds": inside(reading), **reading,
                "at_its_sets": _errors(loss, grads, at_loss, at_grads)}

    shipped = system(task.grad_step, params)
    memory("the system's grad step")
    ref_loss, ref_grads = y.loss_and_grads(params, text2, image2, model,
                                           checkpoint_blocks=True)
    ref_loss, ref_grads = float(ref_loss), host(ref_grads)
    memory("the reference")
    ours = sets_of(task.model, params)
    theirs = np.asarray(y.chosen_experts(params, text2, image2, model))
    differ = np.any(np.sort(ours, -1) != np.sort(theirs, -1), axis=-1)
    say("chosen_experts", sets=int(differ.size),
        sets_that_differ=int(differ.sum()),
        by_layer=differ.sum(axis=(1, 2)).tolist())
    at_shipped = reference_at(ours)
    say("as_shipped", **compared(*shipped, ours))
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
    say("reference_fp8_operands", inside_the_bounds=inside(reading),
        **reading)

    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    # bfloat16 throughout, at its own sets and at the program's
    loss, grads = jax.jit(lambda p: in_lower_precision(p, None))(half)
    reading = _errors(float(loss), host(grads), ref_loss, ref_grads)
    loss, grads = jax.jit(in_lower_precision)(half, jnp.asarray(ours))
    say("reference_in_bf16", inside_the_bounds=inside(reading), **reading,
        at_its_sets=_errors(float(loss), host(grads), *at_shipped))
    del loss, grads

    say("bf16_params", **compared(*system(task.grad_step, half),
                                  sets_of(task.model, half)))
    del half

    def bf16_route(self, a):
        with jax.named_scope("router"):
            scores = jnp.einsum("btd,de->bte", a,
                                self.router.astype(a.dtype))
            top, idx = jax.lax.top_k(scores.astype(jnp.float32),
                                     self.cfg.experts_per_token)
            return idx, jax.nn.softmax(top, axis=-1)
    as_ships, sparse_lm.ExpertLayer.route = \
        sparse_lm.ExpertLayer.route, bf16_route
    try:
        patched = sparse_lm.build(task.model_cfg, task.mesh)
        say("bf16_router", **compared(
            *system(jax.jit(make_grad_step(
                patched, accum_steps=task.trainer_cfg.grad_accum_steps)),
                params), sets_of(patched, params)))
    finally:
        sparse_lm.ExpertLayer.route = as_ships


if __name__ == "__main__":
    main()
