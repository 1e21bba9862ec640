"""CPU rehearsal of the memory account's five per-layer metrics through
``harness.run_cell`` (test-only, as ``benchmark_rehearse.py``):

    python tests/benchmark_tests/memory_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root``'s, whose
``per_layer`` is the repo's own and so names the five. The CPU backend
keeps no allocator statistics, so the task's account is handed a fake
allocator: the owners' sum, 3 MiB of program code, and the accumulate's
operand for as long as a real one holds it (until the next wait for the
loss). Its last line starts with ``REHEARSAL``: never a result.
"""
import functools
import json
import os
import sys
import time
from pathlib import Path

T0 = time.perf_counter()

from benchmark_rehearse import tiny_root  # noqa: E402

from benchmark import harness  # noqa: E402

MIB = 2 ** 20
CODE, RESERVED, LIMIT = 3 * MIB, 5 * MIB, 1 << 40


def fake_allocator(account):
    """``memory_stats`` of a device that holds the account's owners, the
    programs' code and, from an accumulate to the next settled reading,
    the accumulator a second time."""
    held = {"twice": False, "peak": 0}

    def stats():
        used = account.owned_sum + CODE + (
            account.owned.get("accumulator", 0) if held["twice"] else 0)
        held["peak"] = max(held["peak"], used)
        return {"bytes_in_use": used, "bytes_reserved": RESERVED,
                "peak_bytes_in_use": held["peak"], "bytes_limit": LIMIT}

    def telling(method, twice):
        def call(*args):
            held["twice"] = twice
            return method(*args)
        return call
    account.after_accumulate = telling(account.after_accumulate, True)
    account.settled = telling(account.settled, False)
    return stats


def install():
    from dalle_tpu.task import TrainingTask
    real = TrainingTask.memory.func

    def memory(self):
        account = real(self)
        account.device_memory = fake_allocator(account)
        account._absent = None
        return account
    prop = functools.cached_property(memory)
    prop.__set_name__(TrainingTask, "memory")
    TrainingTask.memory = prop


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root")
    install()
    res = harness.run_cell(
        cell, seed=2**31 + 12345, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:4000])
