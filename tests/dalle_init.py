"""``models/dalle.py``'s ``init_params`` for the tests, as one jitted
program; pytest collects nothing here. The program's own runs ``model.init``
op by op, and on the CPU every initialiser of every leaf is then a compile
of its own: 70-230 compiles and 5-15 s a call under the gate's six workers,
before the test has asked anything. Same signature, same leaves."""
import functools

import jax

from dalle_tpu.models import dalle


def init_params(model, rng, batch=2):
    return _program(model, batch)(rng)


@functools.cache
def _program(model, batch):
    return jax.jit(lambda rng: dalle.init_params(model, rng, batch))
