"""Where compiled programs persist between processes and runs.

One rule for every entry point that compiles: when
``JAX_COMPILATION_CACHE_DIR`` is set the operator has placed the cache and
JAX reads the variable itself — nothing is set in code, so nothing can
override it. Otherwise the cache lives in ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the path is part of the cache key and a
directory that moves never hits. A first flagship start compiles for minutes;
every process of one command (the chip-holding trainer, a CPU aux peer)
shares the same directory.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
JAX_OPTION = "jax_compilation_cache_dir"
DEFAULT_DIRNAME = ".jax_cache"
_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the rule's directory and
    return it. Call before the first compile of the process."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    path = str(_CHECKOUT / DEFAULT_DIRNAME)
    jax.config.update(JAX_OPTION, path)
    return path
