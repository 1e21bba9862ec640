"""The compile counter: what JAX's tracing, lowering and compiling cost,
by program and by the span that was open when they ran.

JAX reports three durations for every jitted program, each with the
program's ``fun_name`` (``jax/_src/dispatch.py``): tracing to a jaxpr,
lowering to an MLIR module, and the backend's compile — which, with the
persistent cache on, is the cache load on a hit. This module keeps them
two ways:

- per program (``by_program[fun_name]``), so that "what did ``grad_step``
  cost to trace" is one number wherever its first call fell;
- per span open on the calling thread (``by_span[phase]``, each open span
  from the outermost in), so that what ``setup/train_state`` spends inside
  JAX's machinery is one number.

Seconds are kept two ways as well. ``trace_s`` / ``lower_s`` are JAX's own
(inclusive): a jitted function traced inside another is reported inside the
outer's seconds too, inner first, so inclusive seconds summed over programs
exceed the wall. ``trace_self_s`` / ``lower_self_s`` are an event's seconds
less those of the counted events of the same thread that ended inside its
interval and that no other event has claimed: parts that sum. A backend
compile traces nothing, so ``compile_s`` is its own self form.

``snapshot()["by_site"]`` is a view of the Mosaic call sites' own record
(``ops/pallas/lowering.py``: every traced call of a site times itself
there): calls, keys, seconds, and how much of them went to tracing a
``(site, key)`` over again.

Each backend compile is also a ``jit/compile`` event of the flight ring,
the child of the span it happened in; a compile that comes after the
trainer's first ``loop/step`` has closed logs one WARNING naming the
program and the step (the first step legitimately compiles the gradient
accumulate): a shape that leaks into the loop recompiles every time it
changes, and the loop stands still meanwhile.

JAX's listeners are process-wide and cannot be taken back through the
public API, so there is one :class:`CompileCounter` a process
(:func:`install`), re-pointed at each new tracer. Nothing here imports
JAX until :func:`install` is called.
"""

from __future__ import annotations

import collections
import logging
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from dalle_tpu.obs.trace import Tracer

logger = logging.getLogger(__name__)

PLANE = "train"
STEP_PHASE = "loop/step"
COMPILE_EVENT = "jit/compile"
ACCOUNT_EVENT = "setup/account"
SETUP = "setup/"

#: monitoring event suffix -> the kind it is counted under
_DURATIONS = {"jaxpr_trace_duration": "trace",
              "jaxpr_to_mlir_module_duration": "lower",
              "backend_compile_duration": "compile"}
_EVENTS = {"compilation_cache/cache_hits": "cache_hits",
           "compilation_cache/cache_misses": "cache_misses"}


def program_name(fun_name: object) -> str:
    """JAX reports tracing under the function's name (``grad_step``) and
    lowering and compiling under the module's (``jit(grad_step)``): one
    program, kept under the function's name."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


#: the kinds whose events may hold other counted events
_SELF = ("trace", "lower")
#: a thread's unclaimed events kept: the direct children of its open traces
_UNCLAIMED = 1 << 16
#: asked of ``sys.modules``: this module imports no JAX
_LOWERING = "dalle_tpu.ops.pallas.lowering"


def _tally() -> Dict[str, float]:
    return {"trace_n": 0, "trace_s": 0.0, "trace_self_s": 0.0,
            "lower_n": 0, "lower_s": 0.0, "lower_self_s": 0.0,
            "compile_n": 0, "compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}


class CompileCounter:
    """Sink of ``jax.monitoring``'s compile events. Thread-safe: JAX
    calls the listeners on whichever thread compiled."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self._lock = threading.Lock()
        # .events: this thread's counted events that no later event has
        # claimed, oldest first, as (end, seconds) on time.time(), the clock
        # JAX takes its durations on
        # graftlint: handoff=thread-local
        self._unclaimed = threading.local()
        self.reset(tracer)

    def reset(self, tracer: Optional[Tracer]) -> None:
        """Start counting anew, recording into ``tracer``."""
        with self._lock:
            self.tracer = tracer
            self.total = _tally()
            self.by_program: Dict[str, Dict[str, float]] = {}
            self.by_span: Dict[str, Dict[str, float]] = {}
            #: backend compiles after the first ``loop/step`` closed
            self.after_first_step: List[tuple] = []
            self._accounted = False

    def _self_seconds(self, kind: str, seconds: float) -> float:
        """``seconds`` of an event that ends now, less the seconds of this
        thread's unclaimed events that ended inside it: JAX reports the
        inner event first, and an event that has been taken out of one
        outer is not taken out of the next. A backend compile holds no
        other event and claims none."""
        try:
            events = self._unclaimed.events
        except AttributeError:
            events = self._unclaimed.events = collections.deque(
                maxlen=_UNCLAIMED)
        now = time.time()
        inside = 0.0
        while kind in _SELF and events and events[-1][0] > now - seconds:
            inside += events.pop()[1]
        events.append((now, seconds))
        return max(seconds - inside, 0.0)

    def _count(self, kind: str, seconds: Optional[float],
               program: Optional[str], stack: list) -> None:
        own = None if seconds is None else self._self_seconds(kind, seconds)
        with self._lock:
            rows = [self.total]
            if program is not None:
                rows.append(self.by_program.setdefault(program, _tally()))
            rows.extend(self.by_span.setdefault(s.phase, _tally())
                        for s in stack)
            for row in rows:
                if seconds is None:
                    row[kind] += 1
                else:
                    row[kind + "_n"] += 1
                    row[kind + "_s"] += seconds
                    if kind in _SELF:
                        row[kind + "_self_s"] += own

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        kind = _DURATIONS.get(event.rsplit("/", 1)[-1])
        if kind is None:
            return
        tracer = self.tracer
        program = program_name(kw.get("fun_name", "?"))
        stack = tracer.open_spans() if tracer is not None else []
        self._count(kind, seconds, program, stack)
        if kind != "compile" or tracer is None:
            return
        tracer.event(PLANE, COMPILE_EVENT, program=program,
                     seconds=round(seconds, 6))
        steps_closed = tracer.closed(PLANE, STEP_PHASE)
        if steps_closed:
            where = stack[-1].trace if stack else f"after step:{steps_closed}"
            with self._lock:
                self.after_first_step.append((program, seconds, where))
            logger.warning(
                "compiled %s (%.3f s) after the first step, at %s: a "
                "program of the steady loop should compile in set-up",
                program, seconds, where)

    def on_event(self, event: str, **kw) -> None:
        kind = _EVENTS.get(event.split("/jax/", 1)[-1])
        if kind is None:
            return
        tracer = self.tracer
        self._count(kind, None, None,
                    tracer.open_spans() if tracer is not None else [])

    def cost(self) -> Tuple[int, float]:
        """(backend compiles, seconds tracing + lowering + compiling) so
        far, each second once: what the late-step recorder reads at every
        step's edge."""
        with self._lock:
            total = self.total
            return int(total["compile_n"]), self_seconds(total)

    def account_setup(self) -> Optional[Dict[str, object]]:
        """Where set-up's seconds went, said once after each :meth:`reset`
        when the trainer's first ``loop/step`` has closed: one
        ``setup/account`` event of the ring and one INFO line."""
        with self._lock:
            tracer = self.tracer
            if self._accounted or tracer is None:
                return None
            self._accounted = True
        account = setup_account(tracer.dump(), self.snapshot())
        if account is not None:
            tracer.event(PLANE, ACCOUNT_EVENT, "setup", **account)
            logger.info("%s", account_line(account))
        return account

    def snapshot(self) -> Dict[str, object]:
        # the sites' record is the process's, not this counter's: it is not
        # started anew by ``reset`` (a key traced for an earlier task is
        # traced again for this one)
        lowering = sys.modules.get(_LOWERING)
        by_site = lowering.by_site() if lowering else {}
        with self._lock:
            return {"total": dict(self.total), "by_site": by_site,
                    "by_program": {k: dict(v)
                                   for k, v in self.by_program.items()},
                    "by_span": {k: dict(v) for k, v in self.by_span.items()},
                    "after_first_step": list(self.after_first_step)}


def self_seconds(row: Dict[str, float]) -> float:
    """A tally's seconds of the three kinds, each second once."""
    return row["trace_self_s"] + row["lower_self_s"] + row["compile_s"]


#: how many call sites the account names
ACCOUNT_SITES = 5


def setup_account(rows: List[dict],
                  snap: Dict[str, object]) -> Optional[Dict[str, object]]:
    """From the ring's rows and the counter's snapshot at the first step's
    close: the wall from the first ``setup/*`` span's start to that close,
    each set-up span's wall (``spans``) and the first step's, what of it
    was JAX's machinery (``total``'s self seconds, the cache's hits and
    misses, ``grad_step``'s inclusive trace and lowering), the call sites
    whose tracing took longest as ``[site, trace_s, calls, keys,
    again_s]``, and ``unnamed_s``: the wall that no set-up span and no step
    covers (a harness's own checks between the task and the loop). None
    where the ring holds no set-up span or no closed step."""
    spans = [r for r in rows if r.get("plane") == PLANE and r["dur_s"] > 0]
    setup = [r for r in spans if r["phase"].startswith(SETUP)]
    step = next((r for r in spans if r["phase"] == STEP_PHASE), None)
    if not setup or step is None:
        return None
    start = min(r["t0"] for r in setup)
    wall = step["t0"] + step["dur_s"] - start
    walls: Dict[str, float] = {}
    named, upto = 0.0, start           # the union of the named intervals
    for r in sorted(setup + [step], key=lambda r: r["t0"]):
        if r is not step:
            name = r["phase"][len(SETUP):]
            walls[name] = round(walls.get(name, 0.0) + r["dur_s"], 3)
        end = r["t0"] + r["dur_s"]
        named += max(end - max(r["t0"], upto), 0.0)
        upto = max(upto, end)
    total = snap["total"]
    step_program = snap["by_program"].get("grad_step", _tally())
    by_cost = sorted(snap["by_site"].items(),
                     key=lambda kv: -kv[1]["trace_s"])[:ACCOUNT_SITES]
    return dict(
        wall_s=round(wall, 3), spans=walls,
        first_step_s=round(step["dur_s"], 3),
        unnamed_s=round(wall - named, 3),
        trace_self_s=round(total["trace_self_s"], 3),
        lower_self_s=round(total["lower_self_s"], 3),
        compile_s=round(total["compile_s"], 3),
        cache_hits=total["cache_hits"], cache_misses=total["cache_misses"],
        grad_step_trace_s=round(step_program["trace_s"], 3),
        grad_step_lower_s=round(step_program["lower_s"], 3),
        sites=[[site, round(at["trace_s"], 3), at["calls"], at["keys"],
                round(at["again_s"], 3)] for site, at in by_cost])


def account_line(a: Dict[str, object]) -> str:
    """A ``setup/account`` event in words: the INFO line, and what
    ``chip_smoke.py`` prints."""
    named = ", ".join(f"{name} {s:.1f}" for name, s in a["spans"].items())
    sites = "; ".join(
        f"{site} {seconds:.1f} s in {calls} calls on {keys} keys "
        f"({again:.1f} s of it over again)"
        for site, seconds, calls, keys, again in a["sites"])
    return (f"set-up to the first step's close took {a['wall_s']:.1f} s: "
            f"{named}, first step {a['first_step_s']:.1f}, unnamed "
            f"{a['unnamed_s']:.1f}. Of it JAX traced for "
            f"{a['trace_self_s']:.1f} s, lowered for {a['lower_self_s']:.1f} "
            f"and compiled or loaded for {a['compile_s']:.1f} "
            f"({a['cache_hits']} cache hits, {a['cache_misses']} misses); "
            f"grad_step traced in {a['grad_step_trace_s']:.1f} s and "
            f"lowered in {a['grad_step_lower_s']:.1f}. Mosaic call sites by "
            f"their tracing: {sites or 'none traced'}")


_installed: Optional[CompileCounter] = None
_install_lock = threading.Lock()


def install(tracer: Optional[Tracer]) -> CompileCounter:
    """The process's compile counter, registered with ``jax.monitoring``
    on first call and counting anew into ``tracer`` from now on."""
    global _installed
    with _install_lock:
        if _installed is None:
            import jax.monitoring
            _installed = CompileCounter(tracer)
            jax.monitoring.register_event_duration_secs_listener(
                _installed.on_duration)
            jax.monitoring.register_event_listener(_installed.on_event)
        else:
            _installed.reset(tracer)
        return _installed


def installed() -> Optional[CompileCounter]:
    return _installed
