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
  asked;
- **what a site's tracing costs** (:func:`traced`): every traced call of a
  site brackets the call it makes, kernel or XLA lowering alike, with a
  clock, and its ``(site, key)`` row keeps the calls (``traced_n``), their
  seconds (``trace_s``) and the seconds of the calls after the first
  (``again_s``: what an entry that is traced once a key would not pay).
  The bracket is also a span of the trainer's ring, ``trace/site`` the
  first time a ``(site, key)`` is traced in the process and
  ``trace/site_again`` after that. A forward, its replay and its backward
  rule are separate calls where each passes a bracket (a rule that JAX
  traces at transposition, outside the site's own call, is counted only
  if it brackets itself: the indexer's three kernels do); sites may nest,
  and a site's seconds hold the sites inside it. Only where the process
  counts its compiles
  (``obs.compiles.installed()``): a tool that lowers with no task and no
  counter runs the sites as they were. :func:`by_site` is the record by
  site, the compile counter's ``snapshot()["by_site"]``.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import logging
import threading
import time
from typing import Any, Callable, Dict, Hashable, Iterable, Optional, Tuple

import jax

from dalle_tpu.obs import compiles, trace
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


#: what :func:`traced` keeps in a row, beside the facts a site writes
TIMING = ("traced_n", "trace_s", "again_s")
SITE_PHASE, AGAIN_PHASE = "trace/site", "trace/site_again"

# .key: the key of this thread's last ``chose``, for the form's bracket
# graftlint: handoff=thread-local
_chosen = threading.local()


def record(site: str, key: Hashable, why_not: Optional[str],
           **facts) -> None:
    was = _RECORD.get((site, key), {})
    _RECORD[site, key] = dict({k: was[k] for k in TIMING if k in was},
                              **facts, why_not=why_not)


def chose(site: str, key: Hashable, why_not: Optional[str], words: str,
          **facts) -> bool:
    """A site's answer for a traced call of local shapes ``key``,
    remembered and said (``words``: the refusal, or what the kernel was
    given); whether it is the kernel."""
    record(site, key, why_not, **facts)
    _say(site, why_not is None, words)
    _chosen.key = key
    return why_not is None


def key_digest(key: Hashable) -> str:
    """A key as a span's attribute: short, and the same in every process."""
    return hashlib.sha1(repr(key).encode()).hexdigest()[:8]


@contextlib.contextmanager
def traced(site: str, key: Hashable):
    """Bracket of one traced call of ``site`` at ``key``: its seconds go to
    the record's row and to one span of the ring, the child of whatever
    span is open. Nothing where the process has no compile counter."""
    counter = compiles.installed()
    if counter is None:
        yield
        return
    nth = _RECORD.setdefault((site, key), {"why_not": None}).get(
        "traced_n", 0) + 1
    t0 = time.perf_counter()
    try:
        with trace.span(counter.tracer, compiles.PLANE,
                        SITE_PHASE if nth == 1 else AGAIN_PHASE, site=site,
                        key=key_digest(key), nth=nth):
            yield
    finally:
        seconds = time.perf_counter() - t0
        row = _RECORD[site, key]     # a ``record`` inside wrote it anew
        row.update(traced_n=nth, trace_s=row.get("trace_s", 0.0) + seconds,
                   again_s=row.get("again_s", 0.0) + seconds * (nth > 1))


def by_site() -> Dict[str, Dict[str, float]]:
    """The record's timing by site: traced ``calls`` on ``keys`` distinct
    keys, their ``trace_s``, and of those the ``again_n`` calls of a key
    that had been traced before, with their ``again_s``."""
    sites: Dict[str, Dict[str, float]] = {}
    for (site, _), row in list(_RECORD.items()):
        if not row.get("traced_n"):
            continue
        at = sites.setdefault(site, {"calls": 0, "keys": 0, "trace_s": 0.0,
                                     "again_n": 0, "again_s": 0.0})
        at["calls"] += row["traced_n"]
        at["keys"] += 1
        at["trace_s"] += row["trace_s"]
        at["again_n"] += row["traced_n"] - 1
        at["again_s"] += row["again_s"]
    return sites


def recorded(site: str, key: Hashable) -> Optional[Dict[str, Any]]:
    """What the last traced call of ``site`` at ``key`` said, None where
    none was: the gate is not asked (a site with no gate, a site's own
    facts). The facts alone: what the row's tracing cost is
    :func:`timing`'s."""
    row = _RECORD.get((site, key))
    return row and {k: v for k, v in row.items() if k not in TIMING}


def timing(site: str, key: Hashable) -> Dict[str, float]:
    """The row's ``traced_n``, ``trace_s`` and ``again_s``: zeros where no
    bracket closed on it (no compile counter, or a row that holds a fact
    of another key's call: the expert block's form)."""
    row = _RECORD.get((site, key), {})
    return {k: row.get(k, 0) for k in TIMING}


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
        _chosen.key = None
        took = choose(*operands)
        with traced(name, _chosen.key):
            return (kernel if took else xla)(*operands)
    return per_shard(shard, mesh, in_specs, out_specs, scope=scope)
