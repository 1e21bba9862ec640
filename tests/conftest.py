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
