"""The lowering a looped stack's shipped form is compared with, and nothing
the program runs: the passes as a Python loop over the body
``sparse_lm.run_passes`` scans (``LoopedStack.one_pass``), so that a traced
step holds ``total_ut_steps`` copies of the stack's body where the shipped
form holds one. The leaves, their names and the equations are the scanned
form's; pytest collects nothing here.

    monkeypatch.setattr(sparse_lm, "run_passes", ouro_unrolled.run_passes)

``scripts/ouro_passes_probe.py`` times both on the chip."""
import jax.numpy as jnp

from dalle_tpu.models import sparse_lm
from dalle_tpu.ops.pallas import lowering


def run_passes(stack, x, leaves):
    cfg = stack.cfg
    lowering.record(sparse_lm.LOOP_SITE, sparse_lm._loop_key(cfg), None,
                    form=f"unrolled: {cfg.total_ut_steps} traced passes")
    leaves, exits = stack.as_run(leaves), []
    for _ in range(cfg.total_ut_steps):
        x, z = stack.one_pass(leaves, x)
        exits.append(z)
    return jnp.stack(exits)
