"""One cell of the benchmark, once, with the memory account's five
per-layer metrics on its line.

    python3 scripts/memory_metrics_run.py --workload <cell> --seed <n>
        --seconds 45 --trace 1 [--out-dir chiprun_out/<dir>]

The metrics' files are in ``benchmark/layer_metrics/``
(``loop_in_use_peak_gib``, ``accumulate_transient_gib``,
``memory_unowned_gib``, ``loop_reserved_gib``, ``state_bytes_per_param``;
reducer ``program_attr`` over the ``loop/step``
rows' ``mem_*`` attributes), but ``BENCHMARK.json`` does not name them yet:
``tests/benchmark_tests/test_benchmark_late_steps.py`` holds the list of
per-layer metrics to ending with the four ``late_*`` ones, new entries go
at the end, and a file the benchmark has is a ``benchmark`` PR's to edit
(PERF.md section 7). Until one does, this runs ``benchmark.harness.run_cell``
as ``benchmark/run.py`` does, on a copy of the manifest that has the five
entries appended: ``.benchmark_out/memory_root/BENCHMARK.json``, beside a
link to the benchmark's own directory. It prints what ``benchmark/run.py``
prints; it is no part of the driver's check.
"""
import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402
from pathlib import Path   # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

MEMORY_METRICS = ("loop_in_use_peak_gib", "accumulate_transient_gib",
                  "memory_unowned_gib", "loop_reserved_gib",
                  "state_bytes_per_param")
ENTRY_KEYS = ("name", "unit", "better", "source", "layer", "moves")


def with_entries(data: dict, metrics_dir: Path) -> dict:
    """``BENCHMARK.json``'s object with the five metrics' entries at the
    end of ``per_layer`` (read from their files; no ``workloads`` list:
    every cell reports them)."""
    have = {m["name"] for m in data["per_layer"]}
    files = [json.loads((metrics_dir / f"{name}.json").read_text())
             for name in MEMORY_METRICS if name not in have]
    return dict(data, per_layer=data["per_layer"] + [
        {key: on_file[key] for key in ENTRY_KEYS} for on_file in files])


def root_with_entries(where: Path) -> Path:
    """A manifest root at ``where``: the repo's ``BENCHMARK.json`` with
    the entries, and its benchmark directory by a link."""
    where.mkdir(parents=True, exist_ok=True)
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    (where / "BENCHMARK.json").write_text(json.dumps(
        with_entries(data, ROOT / "benchmark" / "layer_metrics"), indent=1))
    link = where / data["paths"][0]
    if not link.exists():
        os.symlink(ROOT / data["paths"][0], link)
    return where


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--out-dir", type=Path, default=None)
    args = parser.parse_args(argv)

    from benchmark import harness, intervals
    from benchmark.manifest import BenchFailure, Manifest
    out = ROOT / ".benchmark_out"
    try:
        cell = Manifest(root_with_entries(out / "memory_root")).cell(
            args.workload)
        result = harness.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_start=T_START,
            out_dir=args.out_dir or out / (
                f"{cell.name}-seed{args.seed}-trace{args.trace}"))
    except (BenchFailure, intervals.TooFewIntervals) as e:
        print(f"benchmark: no result: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps({"memory_account": ring_account()}))
    print(json.dumps(result), flush=True)
    return 0


def ring_account(last: int = 16) -> dict:
    """What the program's ring holds of the account when the run is over:
    every ``memory/*`` event, and the ``mem_*`` attributes of the last
    ``loop/step`` rows as (least, median, most). An untraced run's line
    has no per-layer metric; this says what its steps read."""
    import statistics

    from dalle_tpu.obs.trace import default_tracer
    rows = default_tracer().dump() if default_tracer() else []
    steps = [r.get("a", {}) for r in rows if r["phase"] == "loop/step"][-last:]
    names = sorted({k for a in steps for k in a if k.startswith("mem_")})
    return {
        "events": [dict(r.get("a", {}), phase=r["phase"], trace=r["trace"])
                   for r in rows if r["phase"].startswith("memory/")],
        "steps": len(steps),
        "least_median_most": {
            name: [f(a[name] for a in steps if name in a)
                   for f in (min, statistics.median, max)]
            for name in names}}


if __name__ == "__main__":
    sys.exit(main())
