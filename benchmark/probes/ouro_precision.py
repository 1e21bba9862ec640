"""What the ``ouro2b6`` tolerance is set from: ``harness.reference_check``
itself, over several seeds in one process, on

- ``system``: the task's own jitted grad step (``TrainingTask.grad_step``,
  the program the window drives) on parameters drawn from the seed as the
  task draws them (no train state is built: ``probes/median_limit.py``);
- ``control``: the reference's equations with both operands of every
  product rounded to float8_e4m3fn (``smallthinker_precision._Fp8Operands``
  standing where the yardstick's ``jnp`` stands: the nearest precision
  below bfloat16 activations that one step's gradients can tell) standing
  where the task stands: its ``correct`` is the harness's own verdict under
  the limits of the configuration's file and has to be false. It runs the
  yardstick's own ``loss_and_grads``, a sequence a call, as the reference
  does (``joyai_precision.float8_control`` differentiates both sequences in
  one program, which this reference's size does not allow beside the
  system's and the reference's gradients).

    python3 -m benchmark.probes.ouro_precision --seeds <n,n,...>
        [--control-seeds <n,n,...>] [--out <dir>]

One JSON line a reading, with the largest distance by this architecture's
kinds of leaf (attention's projections, the gated block's, a layer's four
norms, the final norm, the exit gate and its bias, the embedding, the
head: with no router there are no near-tie sets, so every kind reads
arithmetic), then one line ``summary``: the system's largest and the
control's smallest worst and median leaf and their geometric means. The
float32 reference is computed once a seed (``reference_once``).
"""
import argparse
import json
import math
import types
from pathlib import Path

WORKLOAD = "ouro2b6-train-solo"
KINDS = ("['attn']", "['ff']", "['final_norm']", "_norm']",
         "['exit_gate']", "['exit_gate_bias']", "['token_emb']",
         "['lm_head']")
WHAT = ("correct", "failed", "loss", "reference_loss", "loss_rel_err",
        "grad_rel_l2_max", "grad_rel_l2_worst_leaf", "grad_rel_l2_median")


def by_kind(grads, ref_grads):
    """The largest relative L2 distance of a leaf, by kind of leaf."""
    import jax
    import numpy as np
    worst = dict.fromkeys(KINDS, 0.0)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        g, r = (np.asarray(a, np.float64) for a in (g, r))
        err = float(np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30))
        name = jax.tree_util.keystr(path)
        # by the innermost name: a projection is "attn" or "ff", the final
        # norm is not one of a layer's four
        kind = next(k for k in KINDS if name.endswith(k)
                    or (k in ("['attn']", "['ff']") and k in name))
        worst[kind] = max(worst[kind], err)
    return worst


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default=None)
    parser.add_argument("--out", default=None)
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--root", default=None,
                        help="development: another manifest root (a tiny "
                             "rehearsal root on the CPU)")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = seeds[:3] if args.control_seeds is None else [
        int(s) for s in args.control_seeds.split(",") if s]

    import jax

    from benchmark import harness
    from benchmark.manifest import Manifest
    from benchmark.probes.joyai_precision import reference_once
    from benchmark.probes.smallthinker_precision import _Fp8Operands
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.task import TrainingTask
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = (Manifest(args.root) if args.root else Manifest()).cell(
        args.workload)
    y, model = cell.yardstick, cell.config["model"]
    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, seeds[0]))))
    kept = {}

    def keeping(step):
        """``step``, its gradients kept for the table by kind."""
        def run(params, batch):
            kept["grads"], metrics = step(params, batch)
            return kept["grads"], metrics
        return run

    plain = y.loss_and_grads       # ``reference_once`` stands in its place

    def control(params, batch):
        held, y.jnp = y.jnp, _Fp8Operands()
        try:
            loss, grads = plain(params, batch["text"][:2], batch["image"][:2],
                                model, True)
        finally:
            y.jnp = held
        return grads, {"loss": loss}

    steps = {"system": keeping(task.grad_step), "control": keeping(control)}
    log = None
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        log = open(Path(args.out) / "precision.jsonl", "a")

    def say(line):
        text = json.dumps(line)
        if log:
            log.write(text + "\n")
            log.flush()
        print(text, flush=True)

    read = {name: [] for name in steps}
    with reference_once(y) as forget:
        for seed in dict.fromkeys(seeds + control_seeds):
            forget()
            params = task.family.init_params(
                task.model, jax.random.PRNGKey(seed % harness.SEED_MODULUS))
            for name, step in steps.items():
                if name == "control" and seed not in control_seeds:
                    continue
                verdict = harness.reference_check(types.SimpleNamespace(
                    train_state=types.SimpleNamespace(params=params),
                    mesh=task.mesh, local_batch_size=task.local_batch_size,
                    grad_step=step), cell, seed)
                del verdict["first_batch"]
                verdict["correct"] = verdict["ok"]
                read[name].append(verdict)
                # the seed's one reference, as ``reference_once`` holds it
                _, ref_grads = y.loss_and_grads(None, None, None, None)
                say({"reading": name, "workload": cell.name, "seed": seed,
                     **{k: verdict[k] for k in WHAT},
                     "by_kind": by_kind(kept.pop("grads"), ref_grads)})
            del params
    tol = cell.config["tolerance"]
    summary = {"reading": "summary", "workload": cell.name, "seeds": seeds,
               "control_seeds": control_seeds,
               "limits": {k: v for k, v in tol.items() if k != "reason"}}
    for name, verdicts in read.items():
        if verdicts:
            summary[name] = {
                key: [min(v[key] for v in verdicts),
                      max(v[key] for v in verdicts)]
                for key in ("grad_rel_l2_max", "grad_rel_l2_median",
                            "loss_rel_err")}
            summary[name]["correct"] = [v["correct"] for v in verdicts]
    if read["control"]:
        for key in ("grad_rel_l2_max", "grad_rel_l2_median", "loss_rel_err"):
            low, high = summary["system"][key][1], summary["control"][key][0]
            summary[f"{key}_between"] = {
                "systems_largest": low, "controls_smallest": high,
                "geometric_mean": math.sqrt(low * high)}
    say(summary)
    stats = jax.devices()[0].memory_stats() or {}
    say({"reading": "memory", **{key: stats.get(key) for key in (
        "peak_bytes_in_use", "peak_bytes_reserved")}})


if __name__ == "__main__":
    main()
