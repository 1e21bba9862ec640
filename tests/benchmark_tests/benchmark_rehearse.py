"""CPU rehearsal of the benchmark command at a tiny size (test-only hook).

    python tests/benchmark_tests/benchmark_rehearse.py <chips> <trace 0|1> <out dir> [dtype [yardstick]]

Builds a throw-away manifest root with one tiny cell (the flagship's layer
pattern at the tiny preset's widths: shared axial blocks, scan, conv block;
the ``dalle`` yardstick unless one is named, which may be a file the caller
has put under ``<out dir>/root/benchmark/yardsticks/`` beforehand), then
calls ``harness.run_cell`` with ``require_backend=None`` and interpreted
kernels. What it prints is a rehearsal, never a result: its last line starts
with ``REHEARSAL``. Run it from the root of the repo with ``JAX_PLATFORMS=cpu``
and, for ``chips`` > 1, ``XLA_FLAGS=--xla_force_host_platform_device_count=<chips>``.
"""
import json
import os
import shutil
import sys
import time
from pathlib import Path

T0 = time.perf_counter()

from benchmark_checks import as_run  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.manifest import ROOT, Manifest  # noqa: E402

PATTERN = ("axial_row", "axial_col", "axial_row", "axial_row")


def tiny_dalle(dtype="float32"):
    """Today's tiny cell: the overrides of preset ``tiny`` and the same as
    ``run_trainer`` flags."""
    over = dict(shared_block_cycle=4, attn_types=PATTERN,
                final_conv_block=True, depth=10, scan_unroll=2,
                conv_kernel=3, dtype=dtype)
    args = ["--shared-block-cycle", 4, "--attn-types", *PATTERN,
            "--final-conv-block", "--depth", 10, "--scan-unroll", 2,
            "--conv-kernel", 3, "--dtype", dtype]
    return over, args


def tiny_root(tmp: Path, chips=1, dtype="float32", yardstick=None, *,
              preset="tiny", overrides=None, trainer_args=None):
    """A throw-away manifest root with one cell ``tiny-cell``. ``preset``
    names an entry of ``run_trainer.MODEL_PRESETS`` (a dataclass of any
    class), ``overrides`` the fields set on it and ``trainer_args`` the
    same as ``run_trainer`` flags: the two have to describe one model, or
    ``harness.check_model`` refuses the run. Left out, they are today's
    tiny DALL-E at ``dtype``. So a later PR rehearses a tiny preset of
    another architecture through ``harness.run_cell`` from a test file of
    its own, with its yardstick written under ``tmp`` beforehand."""
    from dalle_tpu.cli.run_trainer import MODEL_PRESETS
    tmp.mkdir(parents=True, exist_ok=True)
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    if overrides is None or trainer_args is None:
        default_over, default_args = tiny_dalle(dtype)
        overrides = default_over if overrides is None else overrides
        trainer_args = default_args if trainer_args is None else trainer_args
    m = as_run(MODEL_PRESETS[preset](**overrides))
    d = tmp / "benchmark"
    for sub in ("configs", "traffic"):
        (d / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("layer_metrics", "yardsticks"):
        shutil.copytree(ROOT / "benchmark" / sub, d / sub,
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    exact = dtype == "float32"
    cfg = {"name": "tiny", "preset": preset, "model": m, "reduced": [],
           "assumed": [], "source": "test", "mosaic_kernels": [],
           "tolerance": {"loss_rel": 1e-4 if exact else 3e-2,
                         "grad_rel_l2": 1e-3 if exact else 0.2,
                         "reason": "test"}}
    if yardstick is not None:
        cfg["yardstick"] = yardstick
    (d / "configs" / "tiny.json").write_text(json.dumps(cfg))
    traffic = {"per_device_batch": 2, "grad_accum_steps": 2,
               "target_batch_size": 1 << 30, "setup_steps": 2,
               "trainer_args": list(trainer_args)}
    (d / "traffic" / "t.json").write_text(json.dumps(traffic))
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "benchmark/configs/tiny.json", "why": "t"}]
    name = "tiny-cell"
    b["workloads"] = [{"name": name, "config": "tiny", "traffic": "t",
                       "chips": chips, "why": "t"}]
    for metric in b["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [name]
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return Manifest(tmp).cell(name)


if __name__ == "__main__":
    chips, trace = (int(a) for a in sys.argv[1:3])
    out = Path(sys.argv[3])
    dtype = sys.argv[4] if len(sys.argv) > 4 else "float32"
    cell = tiny_root(out / "root", chips, dtype, *sys.argv[5:6])
    res = harness.run_cell(
        cell, seed=2**31 + 12345, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True,
        repeat=int(os.environ.get("REPEAT", "1")))
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:3000])
