"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding semantics are exercised without TPUs by spoofing the
host platform device count (the strategy SURVEY.md §4 prescribes; the driver
separately dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).

The XLA flag must be set before jax initializes its backends, hence the env
mutation at import time; the platform is pinned in code so the suite runs on
the CPU whatever JAX_PLATFORMS says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
assert jax.default_backend() == "cpu" and jax.device_count() >= 8


import pytest  # noqa: E402


@pytest.fixture
def lowering_record(monkeypatch):
    """``ops/pallas/lowering.py`` with an empty record of which lowering
    the traced calls took and a log that says every choice again: what a
    test asks (``why_not``, ``recorded``) is what it traced itself. The
    process's own record comes back after the test."""
    from dalle_tpu.ops.pallas import lowering

    monkeypatch.setattr(lowering, "_RECORD", {})
    lowering._say.cache_clear()
    return lowering


@pytest.fixture
def inside_manual_dp():
    """``wrap(vg, mesh, batched, argnums)``: the value-and-grad function
    ``vg(*args) -> ((loss, out), grads)`` run as the gradient accumulation
    runs the model (training/steps.py): under a ``shard_map`` manual over
    ``dp`` alone. Arguments flagged in ``batched`` are split over ``dp``
    on their first axis (as ``out`` and their gradients are); the others
    are replicated, and their gradients summed over ``dp``."""
    from jax.sharding import PartitionSpec as P

    def wrap(vg, mesh, batched, argnums):
        def spec(i):
            return P("dp") if batched[i] else P()

        def shard(*args):
            (loss, out), grads = vg(*args)
            grads = tuple(g if batched[i] else jax.lax.psum(g, "dp")
                          for i, g in zip(argnums, grads))
            return (jax.lax.psum(loss, "dp"), out), grads

        return jax.shard_map(
            shard, mesh=mesh, in_specs=tuple(map(spec, range(len(batched)))),
            out_specs=((P(), P("dp")), tuple(map(spec, argnums))),
            axis_names={"dp"}, check_vma=False)
    return wrap
