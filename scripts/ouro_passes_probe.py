#!/usr/bin/env python
"""The looped stack's two lowerings on the chip, at the cell's sizes: the
passes as ONE traced body (``sparse_lm.run_passes``, what the program
ships) and as a Python loop over the same body (tests/ouro_unrolled.py,
what it is compared with). For each, in a process of its own (a chip
belongs to one process):

    python3 scripts/ouro_passes_probe.py [--form scanned|unrolled|both]
        [--steps 8] [--out chiprun_out/<dir>]

one JSON line: seconds to trace and lower the grad step, to compile it, its
plan (``temp_size_in_bytes``), its executable's size, the Mosaic census and
the median seconds of ``--steps`` steps on parameters drawn from a seed,
with the loss of the last (the two forms' agree to rounding). No train
state is built: the unrolled form's plan beside 14 bytes a parameter of it
is what this probe is for, not a fault of it.
"""
import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def one(form: str, steps: int, preset: str, micro: int, accum: int) -> dict:
    sys.path[:0] = [str(ROOT), str(ROOT / "tests")]
    import statistics

    import jax
    import numpy as np

    from benchmark.harness import kernel_census
    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    from dalle_tpu.models import family, sparse_lm
    from dalle_tpu.parallel.mesh import batch_sharding, make_mesh
    from dalle_tpu.training.steps import make_grad_step
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if form == "unrolled":
        import ouro_unrolled
        sparse_lm.run_passes = ouro_unrolled.run_passes
    cfg = MODEL_PRESETS[preset]()
    mesh = make_mesh()
    module = family(cfg)
    model = module.build(cfg, mesh)
    params = module.init_params(model, jax.random.PRNGKey(67))
    rng = np.random.default_rng(67)
    rows = micro * accum
    batch = jax.device_put(
        {"text": rng.integers(2, cfg.vocab_text, (rows, cfg.text_seq_len),
                              dtype=np.int32),
         "image": rng.integers(0, cfg.vocab_image,
                               (rows, cfg.image_seq_len), dtype=np.int32)},
        batch_sharding(mesh))
    step = jax.jit(make_grad_step(model, accum_steps=accum))
    t = time.perf_counter()
    lowered = step.lower(params, batch)
    trace_lower_s = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t
    plan = compiled.memory_analysis()
    seconds = []
    for _ in range(steps + 1):
        t = time.perf_counter()
        grads, metrics = compiled(params, batch)
        loss = float(metrics["loss"])
        del grads
        seconds.append(time.perf_counter() - t)
    stats = jax.devices()[0].memory_stats() or {}
    return {"form": form, "preset": preset, "micro": micro, "accum": accum,
            "layout": sparse_lm.engagement_records(cfg, mesh)["loop_layout"],
            "trace_lower_s": trace_lower_s, "compile_s": compile_s,
            "plan_gib": plan.temp_size_in_bytes / 2 ** 30,
            "code_mib": plan.generated_code_size_in_bytes / 2 ** 20,
            "census": dict(kernel_census(lowered.as_text())),
            "step_s_median": statistics.median(seconds[1:]),
            "step_s": seconds[1:], "loss": loss,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "peak_bytes_reserved": stats.get("peak_bytes_reserved")}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--form", default="both",
                        choices=("scanned", "unrolled", "both"))
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--preset", default="ouro2b6")
    parser.add_argument("--micro", type=int, default=1)
    parser.add_argument("--accum", type=int, default=2)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    if args.form == "both":
        for form in ("scanned", "unrolled"):
            subprocess.run(
                [sys.executable, __file__, *(argv or sys.argv[1:]),
                 "--form", form], check=False, env=dict(os.environ),
                cwd=ROOT)
        return
    line = one(args.form, args.steps, args.preset, args.micro, args.accum)
    text = json.dumps(line)
    if args.out:
        Path(args.out).mkdir(parents=True, exist_ok=True)
        with open(Path(args.out) / "passes.jsonl", "a") as log:
            log.write(text + "\n")
    print(text, flush=True)


if __name__ == "__main__":
    main()
