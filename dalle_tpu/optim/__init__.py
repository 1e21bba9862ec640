"""Optimizers: LAMB with global clip (fp32) and 8-bit block-quantized LAMB.

:func:`make_optimizer` is the config-driven entry point — it dispatches on
``OptimizerConfig.state_bits`` (the reference default is the 8-bit variant,
``CPULAMB8Bit``, wired at ``task.py:152-161``; the fp32 variant mirrors
``clipped_lamb.py``).
"""

import optax

from dalle_tpu.config import OptimizerConfig
from dalle_tpu.optim.lamb import (  # noqa: F401
    default_wd_mask,
    global_norm,
    lamb,
    lamb_leaf_update,
    make_lr_schedule,
    make_optimizer_fp32,
)
from dalle_tpu.optim.lamb8bit import (  # noqa: F401
    lamb8bit,
    make_optimizer_8bit,
    optimizer_state_bytes,
)


def make_optimizer(cfg: OptimizerConfig,
                   mesh=None) -> optax.GradientTransformation:
    """``mesh``: the device mesh the train state lives on (None = one
    device); the 8-bit state's quantize kernel runs per shard of it."""
    if cfg.state_bits == 8:
        return make_optimizer_8bit(cfg, mesh=mesh)
    if cfg.state_bits == 32:
        return make_optimizer_fp32(cfg)
    raise ValueError(f"unsupported state_bits={cfg.state_bits}")
