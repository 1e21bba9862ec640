"""Does ``memory_stats()["peak_bytes_in_use"]`` count a running program's
temporaries? One jitted program whose large intermediates (2 GiB each)
are neither arguments nor results; the compiler's plan for it beside
the allocator's peak before and after it ran.

    python3 benchmark/probes/allocator_temporaries.py

Prints one JSON line. On the v5e (PR 23) the plan was 4 294 999 552 bytes of
temporaries, ``peak_bytes_in_use`` rose by 1.8 MB, and ``peak_bytes_reserved``
read 4 294 983 680 and stayed reserved after the program had ended: buffers
and program temporaries are counted apart, and ``harness.run_cell`` reports
their sum as ``memory_peak_bytes``.
"""
import json

import jax
import jax.numpy as jnp

N = 32768     # N x N bfloat16 = 2 GiB


def program(x):
    a = jnp.dot(x, x.T)            # N x N: a matrix product is kept whole
    b = jnp.dot(a, a)              # needs all of `a` before any of `b`
    return jnp.sum(jnp.dot(b, a).astype(jnp.float32))


def main() -> None:
    dev = jax.devices()[0]
    x = jnp.full((N, 64), 1e-3, jnp.bfloat16)
    compiled = jax.jit(program).lower(x).compile()
    plan = compiled.memory_analysis()
    before = dict(dev.memory_stats())
    float(compiled(x))
    after = dict(dev.memory_stats())
    print(json.dumps({
        "device_kind": dev.device_kind,
        "plan_temp_bytes": int(plan.temp_size_in_bytes),
        "peak_bytes_in_use_before": before.get("peak_bytes_in_use"),
        "peak_bytes_in_use_after": after.get("peak_bytes_in_use"),
        "memory_stats_after": after}))


if __name__ == "__main__":
    main()
