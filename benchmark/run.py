"""The benchmark's one command: one cell, once, in a new process.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown`` in
a traced run): the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``. Its last key, ``compared``, holds each
number that decided ``correct`` beside its limit; the same are the last
lines of standard error, both by the benchmark's contract: of a run that is
not correct the driver's record keeps the end of each stream and nothing
else. Earlier lines itemise set-up, the reference check
with the census and the program's engagement records, and every window's
step intervals; they decide nothing.
Without a TPU, or with another number of chips than the cell asks for, it
prints no result and exits non-zero.

``--repeat`` and ``--out-dir`` are for development (several windows after
one set-up, outputs under ``chiprun_out/``); the driver passes neither.
"""

import time

T_START = time.perf_counter()   # before anything heavy is imported

import argparse   # noqa: E402
import json       # noqa: E402
import sys        # noqa: E402
from pathlib import Path   # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--repeat", type=int, default=1,
                        help="development: measure this many windows in a "
                             "row after one set-up")
    parser.add_argument("--out-dir", type=Path, default=None,
                        help="development: where intervals, logs and the "
                             "trace land (default .benchmark_out/<run>)")
    args = parser.parse_args(argv)

    from benchmark import harness, intervals
    from benchmark.manifest import ROOT, BenchFailure, Manifest
    try:
        cell = Manifest().cell(args.workload)
        out_dir = args.out_dir or (
            ROOT / ".benchmark_out"
            / f"{cell.name}-seed{args.seed}-trace{args.trace}")
        result = harness.run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), out_dir=out_dir, t_start=T_START,
            repeat=args.repeat)
    except (BenchFailure, intervals.TooFewIntervals) as e:
        print(f"benchmark: no result: {e}", file=sys.stderr, flush=True)
        return 3
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
