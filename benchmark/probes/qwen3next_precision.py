"""What the ``qwen3next80b`` tolerance is set from: the readings of
``probes/lfm2_precision.py`` (``as_shipped``: ``harness.reference_check``
on the task itself; ``at_its_sets``: the program against the reference at
the top-10 sets the program chose, with how many sets differ;
``reference_in_bf16``) on the cell ``qwen3next80b-train-solo``, with the
largest distance by this architecture's kinds of leaf (the mixer's
projections, its taps, its vectors a head and its norm, attention, the
routers, the routed experts, the shared expert and its gate, the embedding
and the head), and a ``control`` of its own.

    python3 -m benchmark.probes.qwen3next_precision --seed <n> [--out <dir>]
        [--readings as_shipped,at_its_sets,reference_in_bf16 | control]

That probe's program is this one's for every reading but the control: it
takes its cell by name and reads every size from the cell's configuration
and yardstick. **The control** (the reference's equations in float32 with
both operands of every product rounded to float8_e4m3fn,
``joyai_precision.float8_control``, standing where the task stands in
``harness.reference_check``: ``correct`` is the harness's own verdict and
has to be false) runs here in a process that holds the parameters and no
trainer, as ``probes/nemotronh_precision.py``'s does and for its reason.
The control's parameters are ``init_params`` of the configuration under the
seed, as ``TrainingTask`` draws them. It rounds the operands of the
yardstick's ``dot`` and ``einsum`` (every projection, attention's two
products, the experts', the head): the recurrence itself (the decay, the
state's read-back, its outer-product write and its read-out) is elementwise
f32 work there and stays so, as the program keeps its states in f32.
"""
import argparse
import json
import types
from pathlib import Path

from benchmark.probes import lfm2_precision as shared

WORKLOAD = "qwen3next80b-train-solo"
KINDS = ("['in_proj']", "['out_proj']", "['taps']", "['A_log']",
         "['dt_bias']", "['gdn']['norm']", "['attn']", "['router']",
         "['experts']", "['shared']", "['shared_gate']", "['token_emb']",
         "['lm_head']")


def control(seed: int, out, workload: str = WORKLOAD, root=None) -> None:
    """One JSON line: the float8 control through ``harness.reference_check``
    with a stand-in that holds the parameters, the mesh and the batch size
    of the cell and the control as its grad step."""
    import jax

    from benchmark import harness
    from benchmark.manifest import Manifest
    from benchmark.probes.joyai_precision import float8_control
    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import family
    from dalle_tpu.parallel.mesh import make_mesh
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    cell = (Manifest(root) if root else Manifest()).cell(workload)
    # the configuration as the file holds it (a rehearsal root's is tiny)
    cfg = type(MODEL_PRESETS[cell.config["preset"]]())(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cell.config["model"].items()})
    mesh = make_mesh()
    module = family(cfg)
    params = module.init_params(
        module.build(cfg, mesh),
        jax.random.PRNGKey(seed % harness.SEED_MODULUS))
    stand_in = types.SimpleNamespace(
        train_state=types.SimpleNamespace(params=params), mesh=mesh,
        local_batch_size=cell.traffic["per_device_batch"]
        * cell.traffic["grad_accum_steps"],
        grad_step=float8_control(cell.yardstick, cell.config["model"]))
    verdict = harness.reference_check(stand_in, cell, seed)
    verdict.pop("first_batch")
    line = {"reading": "control", "seed": seed, "correct": verdict["ok"],
            **{k: verdict[k] for k in (
                "loss", "reference_loss", "loss_rel_err", "grad_rel_l2_max",
                "grad_rel_l2_worst_leaf", "grad_rel_l2_median")}}
    if out:
        Path(out).mkdir(parents=True, exist_ok=True)
        with open(Path(out) / "precision.jsonl", "a") as log:
            log.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out", default=None)
    parser.add_argument("--readings", default="as_shipped,at_its_sets")
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--root", default=None,
                        help="development: another manifest root (a tiny "
                             "rehearsal root on the CPU)")
    args = parser.parse_args(argv)
    readings = args.readings.split(",")
    if readings == ["control"]:
        control(args.seed, args.out, args.workload, args.root)
        return
    if "control" in readings:
        parser.error("the control runs in a process of its own (no trainer "
                     "beside it): --readings control")
    kinds, shared.KINDS = shared.KINDS, KINDS
    try:
        shared.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--readings", args.readings,
                     *(["--out", args.out] if args.out else []),
                     *(["--root", args.root] if args.root else [])])
    finally:
        shared.KINDS = kinds


if __name__ == "__main__":
    main()
