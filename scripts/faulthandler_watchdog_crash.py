#!/usr/bin/env python3
"""Why the late-step recorder takes its stacks under the interpreter lock.

``faulthandler.dump_traceback_later`` dumps every thread's Python stack from
a C thread that holds no interpreter lock: it reads the frames of a thread
that runs meanwhile. This loop arms it around every step, as ISSUE 35 asked
the recorder to, and every step traces a jitted function anew (what a late
step of the trainer does when it recompiles), so that the timer fires while
the main thread is deep inside JAX's tracing.

    python scripts/faulthandler_watchdog_crash.py watchdog   # the C thread
    python scripts/faulthandler_watchdog_crash.py locked     # a Python thread
    python scripts/faulthandler_watchdog_crash.py none       # no dump at all

Each prints ``survived ...`` and exits 0, or dies of the signal. On Python
3.12.12 with jax 0.9.0 on the CPU (PERF.md section 6, PR 35): ``watchdog``
died of a segmentation fault in 6 of 6 runs, ``locked`` (what
``obs/late.py``'s pulse does: a Python thread walks ``sys._current_frames``,
holding the lock while it reads) and ``none`` in 0 of 5 each.
"""
import faulthandler
import os
import sys
import threading

os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax                   # noqa: E402
import jax.numpy as jnp      # noqa: E402

STEPS = 30
AFTER_S = 0.05


def model(x):
    for _ in range(12):
        x = jnp.tanh(x @ x.T @ x) + jax.nn.softmax(x, axis=-1)
        x = jax.lax.cond(x.sum() > 0, lambda y: y * 2, lambda y: y - 1, x)
    return x.sum()


def main(how: str) -> None:
    out = open(os.devnull, "wb")
    halt = threading.Event()

    def locked():
        while not halt.wait(AFTER_S):
            for frame in sys._current_frames().values():
                while frame is not None:
                    out.write(f"{frame.f_code.co_filename}:{frame.f_lineno} "
                              f"{frame.f_code.co_name}\n".encode())
                    frame = frame.f_back

    if how == "locked":
        threading.Thread(target=locked, daemon=True).start()
    for n in range(STEPS):
        if how == "watchdog":
            faulthandler.dump_traceback_later(AFTER_S, file=out)
        step = jax.jit(jax.grad(model))        # a new function: traced again
        step(jnp.ones((8 + n % 5, 16))).block_until_ready()
        faulthandler.cancel_dump_traceback_later()
    halt.set()
    print(f"survived {STEPS} steps with {how}: Python "
          f"{sys.version.split()[0]}, jax {jax.__version__}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "watchdog")
