"""Which lowering runs at a Mosaic call site, and which ran.

Every Pallas kernel of the models has an XLA lowering of the same math
beside it, and a predicate on the local shapes (``*_fits``, ``None`` or why
not) that chooses between them at trace time. This module owns the rest of
that choice, so that a site is a predicate, a kernel, a lowering and their
specs:

- **the gate**: :func:`mosaic` says whether there is a Mosaic backend at
  all (a TPU, or the tests' interpret flag), :func:`interpret` is what the
  kernels take as their ``interpret`` argument;
- **the form** (:func:`site`): gate shut, the XLA lowering on the whole
  arrays (so a mesh of CPU devices gets it under GSPMD); gate open,
  ``parallel/mesh.per_shard`` of "fits? the kernel : the XLA lowering"
  under the site's scope;
- **the record**: one table, ``(site, key) -> facts`` (``why_not``, None
  where the call took the kernel, and whatever else the site said of it:
  the backward's kind, the operands' form, the sum's tile), written by the
  call that logs the choice (:func:`chose`; the log says a thing once, the
  record is written at every trace). ``site`` is the name the log line
  starts with; ``key`` is everything the choice was made from (the local
  shapes), so another model traced in the process neither vouches for this
  one nor taints it. It is process-wide because it has to survive the jit
  cache: a program traced once (the benchmark's reference check) is not
  traced again by whoever asks next (``warmup``);
- **the question**: :func:`why_not` (``None`` where the last traced call of
  those shapes took the kernel, else the refusal, :data:`NONE_TRACED`, or
  :data:`NO_BACKEND` where the gate is shut), :func:`first_refusal` for
  "all of these took it, or the first refusal says why", and
  :func:`recorded` for a call's facts as they were written, the gate not
  asked.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Callable, Dict, Hashable, Iterable, Optional, Tuple

import jax

from dalle_tpu.parallel.mesh import per_shard

logger = logging.getLogger(__name__)

NO_BACKEND = "no Mosaic backend"
NONE_TRACED = "none traced"


def interpret() -> bool:
    """The kernels' ``interpret`` argument: tests run them on the CPU."""
    # the one read of a model module's attribute from below it: the flag's
    # storage stays where the benchmark's harness, chip_smoke.py and the
    # tests write it (ROADMAP Design 11b)
    from dalle_tpu.models import attention
    return attention._PALLAS_INTERPRET


def mosaic() -> bool:
    """Whether a Mosaic kernel can run here. ``jax.default_backend`` is
    looked up through the module at call time: the tools that lower for a
    described chip from a CPU box replace that attribute."""
    return jax.default_backend() == "tpu" or interpret()


_RECORD: Dict[Tuple[str, Hashable], Dict[str, Any]] = {}


@functools.lru_cache(maxsize=None)
def _say(site: str, kernel: bool, why: str) -> None:
    """Once per distinct (site, choice, reason), so a run that quietly gave
    a fused kernel up for the XLA lowering shows it in its log."""
    logger.info("%s: %s (%s)", site,
                "Pallas kernel" if kernel else "XLA lowering", why)


def record(site: str, key: Hashable, why_not: Optional[str],
           **facts) -> None:
    _RECORD[site, key] = dict(facts, why_not=why_not)


def chose(site: str, key: Hashable, why_not: Optional[str], words: str,
          **facts) -> bool:
    """A site's answer for a traced call of local shapes ``key``,
    remembered and said (``words``: the refusal, or what the kernel was
    given); whether it is the kernel."""
    record(site, key, why_not, **facts)
    _say(site, why_not is None, words)
    return why_not is None


def recorded(site: str, key: Hashable) -> Optional[Dict[str, Any]]:
    """What the last traced call of ``site`` at ``key`` said, None where
    none was: the gate is not asked (a site with no gate, a site's own
    facts)."""
    return _RECORD.get((site, key))


def why_not(site: str, key: Hashable) -> Optional[str]:
    if not mosaic():
        return NO_BACKEND
    return _RECORD.get((site, key), {"why_not": NONE_TRACED})["why_not"]


def first_refusal(asked: Iterable[Tuple[str, Hashable]]) -> Optional[str]:
    """None where every ``(site, key)`` took its kernel: calls that share
    one lowering in words, all of which took it or the first refusal says
    why none did."""
    return next(filter(None, (why_not(*a) for a in asked)), None)


def site(name: str, choose: Callable[..., bool], kernel: Callable,
         xla: Callable, mesh=None, in_specs=None, out_specs=None,
         scope: Optional[str] = None) -> Callable:
    """A call site's function of its operands. ``choose(*local operands)``
    is the site's predicate behind :func:`chose`; ``kernel`` and ``xla``
    take the same operands."""
    if not mosaic():
        _say(name, False, NO_BACKEND)
        return xla

    def shard(*operands):
        return (kernel if choose(*operands) else xla)(*operands)
    return per_shard(shard, mesh, in_specs, out_specs, scope=scope)
