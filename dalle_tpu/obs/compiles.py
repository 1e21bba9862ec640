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

import logging
import threading
from typing import Dict, List, Optional, Tuple

from dalle_tpu.obs.trace import Tracer

logger = logging.getLogger(__name__)

PLANE = "train"
STEP_PHASE = "loop/step"
COMPILE_EVENT = "jit/compile"

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


def _tally() -> Dict[str, float]:
    return {"trace_n": 0, "trace_s": 0.0, "lower_n": 0, "lower_s": 0.0,
            "compile_n": 0, "compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}


class CompileCounter:
    """Sink of ``jax.monitoring``'s compile events. Thread-safe: JAX
    calls the listeners on whichever thread compiled."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self._lock = threading.Lock()
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

    def _count(self, kind: str, seconds: Optional[float],
               program: Optional[str], stack: list) -> None:
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
        far: what the late-step recorder reads at every step's edge."""
        with self._lock:
            total = self.total
            return int(total["compile_n"]), (
                total["trace_s"] + total["lower_s"] + total["compile_s"])

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"total": dict(self.total),
                    "by_program": {k: dict(v)
                                   for k, v in self.by_program.items()},
                    "by_span": {k: dict(v) for k, v in self.by_span.items()},
                    "after_first_step": list(self.after_first_step)}


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
