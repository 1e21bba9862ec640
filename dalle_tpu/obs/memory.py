"""The memory account: what holds the device's memory, by owner, by phase
of a step and at a failed allocation.

``memory_stats()`` gives two process-wide peaks that only grow. Read from
outside after a run they say how full the chip once was, not what filled
it, nor whether it was the loop or something in set-up. So the trainer
keeps its own account, always on, like the ring it records into:

- **Owners**, exact, from the trees and no allocator: the bytes that
  ``params``, ``opt_state``, the swarm's gradient accumulator, the grad
  step's output and the batch in hand keep on the read device (the summed
  ``nbytes`` of their addressable shards there), and the parameter count.
  One ring event :data:`OWNERS_EVENT` when the train state is built and
  one when the first accumulate has made the accumulator; one sentence,
  ``memory_layout``, on the ``setup/warmup`` row (:meth:`layout`).
- **Phases of a step**, from the allocator, sampled where the loop
  already stands and never by waiting for the device: right after
  ``loop/grad_dispatch``, right after ``loop/loss_wait`` (the loop has
  just waited for the device for its own reasons, so everything it was
  given has run: the one reading of a step that is no race, the step's
  **settled** one), right after ``collab/accumulate``, and at the step's
  edge (the reading the late-step recorder shares: :attr:`edge`; it comes
  a millisecond after the accumulate's dispatch, the allocator gives the
  accumulate's operands back some 9 ms after it: PERF.md section 6,
  PR 41). Each ``loop/step`` row carries :data:`STEP_ATTRIBUTES`.
- **Where the process's peak last rose**: ``peak_bytes_in_use`` cannot be
  reset, so every reading that finds it higher than the reading before
  writes one :data:`PEAK_EVENT`. A run whose last such event is of set-up
  has a loop that never passed set-up's peak, and ``mem_step_max`` is
  then the loop's own high-water mark.
- **Near the limit and at a failed allocation**: :data:`NEAR_EVENT` with
  one WARNING (at most one in :data:`WARN_EVERY_S` seconds) from an edge
  that finds the device over :data:`NEAR_LIMIT_SHARE` of its limit, and
  :data:`EXHAUSTED_EVENT` with one ERROR when ``train_loop`` catches the
  runtime's ``RESOURCE_EXHAUSTED`` (which it raises again, unchanged).
  Both hold the owners, the samples, the open span and, the second, the
  program.

A source the machine has in name only (the CPU backend's
``memory_stats()`` is None) is judged at the first reading and costs no
call from then on, as ``late.py`` leaves out its own (``SOURCES``); the
owners need no allocator and are kept all the same. Nothing here imports
JAX: ``TrainingTask`` hands in the read device's ``memory_stats`` and the
function that weighs a tree there.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

from dalle_tpu.obs.late import WARN_EVERY_S
from dalle_tpu.obs.trace import Tracer

logger = logging.getLogger(__name__)

PLANE = "train"
OWNERS_EVENT = "memory/owners"
PEAK_EVENT = "memory/peak_rose"
NEAR_EVENT = "memory/near_limit"
EXHAUSTED_EVENT = "memory/exhausted"

#: the trees the trainer keeps on the device, in the order they are made
OWNERS = ("params", "optimizer", "accumulator", "step_output", "batch")
#: a step's samples of ``bytes_in_use``, in the order they are taken
SAMPLES = ("mem_after_grad", "mem_settled", "mem_after_accumulate",
           "mem_edge")
#: what every ``loop/step`` row carries (GiB, as the benchmark's ``*_gib``
#: metrics read them, but the last; the events hold bytes): the four
#: samples and the largest of them, what the accumulate holds beyond the
#: settled reading, what of the settled reading no owner explains (the
#: loaded programs' code, for one), the allocator's reservation, and the
#: owners' sum over the parameter count
STEP_ATTRIBUTES = SAMPLES + (
    "mem_step_max", "mem_accumulate_transient", "mem_unowned",
    "mem_reserved", "mem_state_bytes_per_param")
#: an edge over this share of ``bytes_limit`` is near the limit
NEAR_LIMIT_SHARE = 0.95
#: how the runtime's error for a failed allocation starts, whatever raised
EXHAUSTED = "RESOURCE_EXHAUSTED"
#: where no span is open
SETUP = "setup"
#: the jitted program that runs under each span of the loop in which the
#: device allocates, under the name the compile counter keeps it
PROGRAMS = {"setup/warmup": "grad_step", "loop/grad_dispatch": "grad_step",
            "loop/loss_wait": "grad_step",
            "collab/accumulate": "accumulate_grads",
            "collab/reconcile": "apply_step",
            "collab/global_step": "apply_step"}
#: the ring's last rows in which a failed allocation's spans are looked
#: for: the loop's nesting is four deep
UNWOUND = 16

GB = 1e9
GIB = 2.0 ** 30


def is_exhausted(exc: BaseException) -> bool:
    """The runtime could not allocate: XLA's ``RESOURCE_EXHAUSTED`` (the
    compiler's "ran out of memory in memory space hbm" and the
    allocator's "error allocating device buffer" alike)."""
    return EXHAUSTED in str(exc)


class MemoryAccount:
    """``device_memory`` returns the read device's ``memory_stats()`` (or
    None), ``tree_bytes(tree, itemsize=None)`` the bytes a tree's leaves
    keep on that device and their element count (with ``itemsize``: the
    bytes leaves of that width and the same shapes and placement would),
    ``compiles`` is the process's compile counter. A test injects all
    three and the clock."""

    def __init__(self, tracer: Tracer,
                 device_memory: Optional[Callable[[], Optional[dict]]] = None,
                 tree_bytes: Optional[Callable[..., Tuple[int, int]]] = None,
                 compiles=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.tracer = tracer
        self.device_memory = device_memory
        self.tree_bytes = tree_bytes
        self.compiles = compiles
        self._clock = clock
        self.owned: Dict[str, int] = {}
        self.parameters = 0
        #: the allocator is absent or in name only (None: not judged yet)
        self._absent: Optional[bool] = None if device_memory else True
        #: the last edge's ``memory_stats()``: what the late-step recorder
        #: reads in place of a second call
        self.edge: Optional[dict] = None
        #: the step's samples so far (bytes in use), by attribute
        self._samples: Dict[str, int] = {}
        self._reserved = 0
        #: the last closed step's account, as its row has it
        self._closed: Dict[str, int] = {}
        #: the owners' sum as it stood at the step's settled reading
        self._settled_owned = 0
        self._peak: Optional[int] = None
        #: where the last reading was taken, for the next ``peak_rose``
        self._read_at = "start"
        self._warned_at: Optional[float] = None
        #: the accumulator has been weighed (as made, not from shapes) and
        #: the second ``memory/owners`` event written
        self._owners_written = False

    # -- owners ------------------------------------------------------------

    def own(self, **trees: Any) -> None:
        """Weigh these owners' trees on the read device."""
        if self.tree_bytes is None:
            return
        for name, tree in trees.items():
            self.owned[name], count = self.tree_bytes(tree)
            if name == "params":
                self.parameters = count

    @property
    def owned_sum(self) -> int:
        return sum(self.owned.values())

    def owners_record(self) -> Dict[str, Any]:
        """The owners in numbers: bytes each, their sum, the parameter
        count, and the sum over it."""
        record: Dict[str, Any] = {name: self.owned[name] for name in OWNERS
                                  if name in self.owned}
        record.update(owned=self.owned_sum, parameters=self.parameters)
        if self.parameters:
            record["bytes_per_param"] = round(
                self.owned_sum / self.parameters, 4)
        return record

    def state_built(self, params: Any, opt_state: Any) -> None:
        """``setup/train_state`` has closed: the first owners, and the
        first reading."""
        self.own(params=params, optimizer=opt_state)
        self.tracer.event(PLANE, OWNERS_EVENT, SETUP, **self.owners_record())
        self.read("setup/train_state closed")

    def step_traced(self, step_output: Any, batch: Any) -> str:
        """The warm-up has run the grad step: its output and its batch are
        owners, the accumulator will be one of the output's shapes and
        placement in f32 (how the swarm optimizer makes it). Returns
        :meth:`layout`."""
        self.own(step_output=step_output, batch=batch)
        if self.tree_bytes is not None and "accumulator" not in self.owned:
            self.owned["accumulator"], _ = self.tree_bytes(step_output[0],
                                                           itemsize=4)
        return self.layout()

    def layout(self) -> str:
        """The owners in one sentence: ``504.1 M parameters: params 4.00
        B, optimizer 2.03, accumulator 4.00, step output 4.00, batch 0.00
        = 14.03 B a parameter resident (7.07 GB)``."""
        n = max(self.parameters, 1)
        parts = [f"{name.replace('_', ' ')} {self.owned[name] / n:.2f}"
                 for name in OWNERS if name in self.owned]
        if not parts:
            return "no owner weighed"
        parts[0] += " B"
        return (f"{self.parameters / 1e6:.1f} M parameters: "
                f"{', '.join(parts)} = {self.owned_sum / n:.2f} B a "
                f"parameter resident ({self.owned_sum / GB:.2f} GB)")

    # -- the allocator -------------------------------------------------------

    def read(self, at: str) -> Optional[dict]:
        """The read device's ``memory_stats()`` now, or None where the
        source is absent; ``at`` says where the program stands. Notes the
        process's peak: a reading that finds it above the one before
        writes one :data:`PEAK_EVENT`."""
        if self._absent:
            return None
        stats = self.device_memory()
        if self._absent is None:
            self._absent = not (stats and stats.get("bytes_in_use"))
            if self._absent:
                logger.info("memory account: this backend's allocator "
                            "keeps no statistics; the owners are weighed "
                            "all the same")
                return None
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            if self._peak is not None and peak > self._peak:
                span, trace = self._where()
                self.tracer.event(
                    PLANE, PEAK_EVENT, **{"from": self._peak, "to": peak,
                                          "span": span, "at": trace,
                                          "read_at": at,
                                          "since": self._read_at})
            self._peak = peak
        self._read_at = at
        return stats

    def _where(self) -> Tuple[str, str]:
        """(the innermost span open on this thread, its trace): which
        step it is, or ``setup``."""
        stack = self.tracer.open_spans()
        return (stack[-1].phase, stack[-1].trace) if stack else (SETUP, SETUP)

    def _sample(self, name: str, at: str) -> Optional[dict]:
        stats = self.read(at)
        if stats is not None:
            self._samples[name] = stats["bytes_in_use"]
            self._reserved = max(self._reserved,
                                 stats.get("bytes_reserved", 0))
        return stats

    # -- a step (train_loop and the swarm optimizer call these) -------------

    def start(self) -> None:
        """The loop is about to open its first step: the first edge.
        Before the late-step recorder's own start, which reads
        :attr:`edge`."""
        self._samples, self._reserved = {}, 0
        self.edge = self.read("loop start")

    def after_grad(self, step_output: Any, batch: Any) -> None:
        """``loop/grad_dispatch`` has returned."""
        if "step_output" not in self.owned:       # no warm-up weighed them
            self.own(step_output=step_output, batch=batch)
        self._sample("mem_after_grad", "loop/grad_dispatch returned")

    def settled(self) -> None:
        """``loop/loss_wait`` has returned: the device has run all it was
        given, and holds what the trainer's trees hold and little else."""
        self._sample("mem_settled", "loop/loss_wait returned")
        # the accumulator weighed from shapes at the warm-up is not made
        # before the first accumulate
        self._settled_owned = self.owned_sum - (
            0 if self._owners_written else self.owned.get("accumulator", 0))

    def after_accumulate(self, accumulator: Any) -> None:
        """``collab/accumulate`` has returned, with the accumulator it
        made."""
        if not self._owners_written:
            self._owners_written = True
            self.own(accumulator=accumulator)
            self.tracer.event(PLANE, OWNERS_EVENT, **self.owners_record())
        self._sample("mem_after_accumulate", "collab/accumulate returned")

    def close_step(self, row) -> None:
        """The step's body is over and its span about to close: read the
        edge, put the step's account on its row (``row.set``), and say so
        if the device is near its limit."""
        if self.parameters:
            row.set(mem_state_bytes_per_param=round(
                self.owned_sum / self.parameters, 4))
        self.edge = self._sample("mem_edge", "step edge")
        samples, self._samples = self._samples, {}
        reserved, self._reserved = self._reserved, 0
        if self.edge is None:
            return
        attrs = self._closed = dict(
            samples, mem_step_max=max(samples.values()),
            mem_reserved=reserved)
        if "mem_settled" in samples:
            attrs["mem_unowned"] = samples["mem_settled"] - self._settled_owned
            if "mem_after_accumulate" in samples:
                attrs["mem_accumulate_transient"] = (
                    samples["mem_after_accumulate"] - samples["mem_settled"])
        row.set(**{name: round(held / GIB, 6)
                   for name, held in attrs.items()})
        limit = self.edge.get("bytes_limit")
        if limit and (samples["mem_edge"] + self.edge.get("bytes_reserved", 0)
                      > NEAR_LIMIT_SHARE * limit):
            self._near_limit(attrs)

    # -- near the limit, and past it ------------------------------------------

    def _record(self, samples: Mapping[str, int]) -> Dict[str, Any]:
        span, trace = self._where()
        record: Dict[str, Any] = {"span": span, "at": trace}
        record.update(self.owners_record())
        record.update(samples)
        for key in ("bytes_in_use", "bytes_reserved", "bytes_limit",
                    "peak_bytes_in_use", "largest_alloc_size"):
            if self.edge and key in self.edge:
                record[key] = self.edge[key]
        return record

    def _near_limit(self, attrs: Mapping[str, int]) -> None:
        record = self._record(attrs)
        self.tracer.event(PLANE, NEAR_EVENT, **record)
        now = self._clock()
        if (self._warned_at is not None
                and now - self._warned_at < WARN_EVERY_S):
            return
        self._warned_at = now
        logger.warning("%s", event_line({"phase": NEAR_EVENT, "a": record}))

    def exhausted(self, exc: BaseException) -> bool:
        """``train_loop`` caught ``exc``. If the runtime could not
        allocate, write what held the device (one :data:`EXHAUSTED_EVENT`,
        one ERROR) and return True; the caller raises ``exc`` again
        either way. The span is the innermost that closed with an error
        (the ring has it: the exception has unwound the spans by now), the
        program the one that runs under it (:data:`PROGRAMS`), if the
        compile counter has counted one of that name."""
        if not is_exhausted(exc):
            return False
        failed = None
        for row in reversed(self.tracer.since(self.tracer.mark() - UNWOUND)):
            if row["plane"] != PLANE:
                continue               # another thread's: a round's hops
            if "error" in row.get("a", {}):
                failed = row
            elif failed is not None:
                break                  # older than this unwinding
        span, trace = ((failed["phase"], failed["trace"]) if failed
                       else self._where())
        self.edge = self.read("allocation failed") or self.edge
        # of the step that failed, or of the last that closed
        record = self._record(self._samples or self._closed)
        record.update(span=span, at=trace)
        program = PROGRAMS.get(span)
        if program is not None and (
                self.compiles is None
                or program in self.compiles.snapshot()["by_program"]):
            record["program"] = program
        record["message"] = str(exc).strip().splitlines()[0][:240]
        self.tracer.event(PLANE, EXHAUSTED_EVENT, trace, **record)
        logger.error("%s", event_line({"phase": EXHAUSTED_EVENT,
                                       "a": record}))
        return True


def memory_line(head: str, r: Mapping[str, Any]) -> str:
    """A ``memory/near_limit`` or ``memory/exhausted`` record in words:
    the log line's text, and what ``scripts/trace_report.py`` prints."""
    gb = lambda key: f"{r[key] / GB:.2f}"
    said = []
    owners = [f"{name.replace('_', ' ')} {gb(name)}" for name in OWNERS
              if name in r]
    if owners:
        said.append(f"owners {', '.join(owners)} = {gb('owned')} GB"
                    + (f" ({r['bytes_per_param']:.2f} B a parameter of "
                       f"{r['parameters'] / 1e6:.1f} M)"
                       if "bytes_per_param" in r else ""))
    samples = [f"{what} {gb(key)}" for key, what in (
        ("mem_after_grad", "after the grad step's dispatch"),
        ("mem_settled", "settled"),
        ("mem_after_accumulate", "after the accumulate"),
        ("mem_edge", "at the edge")) if key in r]
    if samples:
        said.append(f"in use {', '.join(samples)} GB")
    now = [f"{what} {gb(key)}" for key, what in (
        ("bytes_in_use", "in use"), ("bytes_reserved", "reserved"),
        ("bytes_limit", "limit"), ("peak_bytes_in_use", "peak"),
        ("largest_alloc_size", "largest allocation")) if key in r]
    if now:
        said.append(f"now {', '.join(now)} GB")
    if "message" in r:
        said.append(f"the runtime said: {r['message']}")
    return f"{head}: " + "; ".join(said)


def event_line(row: Mapping[str, Any]) -> str:
    """One of the account's ring events in words, for
    ``scripts/trace_report.py``: who owns what, where the process's peak
    rose, what held a device near its limit or past it."""
    a, phase = row.get("a", {}), row["phase"]
    if phase == PEAK_EVENT:
        return (f"{a['at']}: the process's peak rose from "
                f"{a['from'] / GB:.2f} to {a['to'] / GB:.2f} GB, seen at "
                f"{a['read_at']} (in {a['span']}, since {a['since']})")
    if phase == OWNERS_EVENT:
        return memory_line(f"{row['trace']}: the trainer's trees", a)
    if phase == EXHAUSTED_EVENT:
        return memory_line(
            f"{a['at']}: the device could not allocate in {a['span']}"
            + (f" (program {a['program']})" if "program" in a else ""), a)
    return memory_line(
        f"{a['at']}: the device holds over {NEAR_LIMIT_SHARE:.0%} of its "
        f"{a['bytes_limit'] / GB:.2f} GB", a)
