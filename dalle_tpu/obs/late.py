"""The late-step recorder: for every step of the trainer's loop that ran
over, where the time went and what the machine, the process, the
interpreter and the device were doing meanwhile.

A span says *where* the loop stood (``loop/loss_wait``), not *why* it
stood there: the wait for the loss is long both when the device is late
and when this process was not running. So ``train_loop`` opens and closes
every step through :meth:`LateSteps.step`, which

- keeps the durations of the last :data:`HISTORY` ``loop/step`` spans and
  of their child phases (read back from the flight ring: this module
  reads no clock for them), and calls a step **late** when it is over
  their median by both :data:`LATE_SHARE` and :data:`LATE_FLOOR_S`, round
  work that is long by design (:data:`ROUND_WORK`) taken out first and
  the time since the step before closed (:data:`BETWEEN`) counted in;
- reads at each step's close (the previous close is the next open) what
  the kernel has charged the process and the machine, what the collector
  and the compile counter have counted, what the fullest device holds,
  and by how much the **pulse** — one daemon thread that wakes every
  :data:`BEAT_S` seconds — found its wake-ups late;
- records after a late step's ``loop/step`` row one ring event
  ``loop/late_step`` whose attributes are those numbers and the first
  **cause** of :data:`CAUSES` they support, and logs one WARNING.

**Stacks.** Where a step has been open :data:`STACKS_AFTER` times the
usual *while the pulse was beating* (what it missed does not count), the
pulse writes every Python thread's stack, once a step, to
``<trace-file>.stacks`` or standard error, and the record holds the
innermost frame of every thread: the host was alive, and the dump says
where the loop stood (a hook, the batch's fetch, the wait for the device).
It is taken by the pulse, a Python thread that holds the interpreter lock
meanwhile (``sys._current_frames``), so no frame moves under it; and so
none is taken *during* a stall in which a native call kept the lock or the
process stood still, nor after one, when every thread is back in a wait
and a dump names nothing: ``busiest_thread`` names a thread that burned the
CPU meanwhile, ``pulse_lock_waits`` says that one kept the lock.
**Not** CPython's watchdog (``faulthandler.dump_traceback_later``), whose C
thread needs no lock and could write during such a stall: it reads other
threads' frames while they run. Armed around every step of a loop whose
steps trace a jitted function anew (what a late step that recompiles
does), it took the process down with a segmentation fault in 6 of 6 runs
(``scripts/faulthandler_watchdog_crash.py``, PERF.md section 6, PR 35).
Nothing here arms a ``faulthandler`` timer.

Every source that is absent (no ``/proc/pressure``, no ``cpu.stat``, a
backend with no ``memory_stats``) is left out of the record, never an
error, and so is one that this machine has in name only (a sandbox's
``/proc/stat`` that does not tick, its ``getrusage`` that counts no
switch, an allocator that counts no allocation): :data:`SOURCES`, judged
once, over the steps before any can be late. Nothing here imports JAX: the
compile counter and the device's memory reader are handed in by
``TrainingTask``.
"""

from __future__ import annotations

import contextlib
import gc
import logging
import os
import resource
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from dalle_tpu.obs.trace import Tracer

logger = logging.getLogger(__name__)

PLANE = "train"
STEP_PHASE = "loop/step"
LATE_EVENT = "loop/late_step"

#: steps whose median is "usual"
HISTORY = 32
#: with fewer closed steps than this none is late
MIN_CLOSED = 4
#: a step is late when it is over the usual by BOTH of these. 33 windows
#: of the sparse cells (PERF.md section 6, PR 35): the smallest stall was
#: 1.4% of its step, the largest quiet scatter 0.15%
LATE_SHARE = 0.01
LATE_FLOOR_S = 0.005
#: the pulse takes every thread's stack once a step has been open this
#: many times the usual while it beat, and never sooner than the floor
STACKS_AFTER = 1.25
STACKS_FLOOR_S = 0.05
#: the pulse's period, and the lateness of a wake-up below which it is
#: ordinary scheduling and not added up
BEAT_S = 0.02
BEAT_SLACK_S = 0.002
#: CPython's switch interval: a thread that waits for the interpreter
#: lock wakes this often to ask for it, each time a voluntary switch
LOCK_WAIT_S = 0.005
#: a process whose kernel counts voluntary switches shows at least this
#: many a second (the pulse alone makes fifty)
LIVE_SWITCHES_A_SECOND = 5
#: /proc/stat counts in ticks, one CPU at a time: a shortfall of the
#: machine's own seconds below this is rounding
TICK_SLACK_S = 0.02
#: at most one WARNING in this many seconds; the next names those held
#: back, the first so many of them
WARN_EVERY_S = 30.0
HELD_SHOWN = 8

#: rows of a step that are long by design when a swarm round turns over;
#: they have spans of their own and wait for the swarm cell's metrics
ROUND_WORK = frozenset({"collab/global_step", "collab/resync",
                        "collab/launch_round", "collab/reconcile",
                        "loop/epoch_report"})
#: the two phases in which the loop waits for the device. An untraced run
#: and production wait in ``loop/loss_wait``; the benchmark's traced run
#: patches ``grad_step`` to wait inside ``loop/grad_dispatch``
DEVICE_WAIT = frozenset({"loop/grad_dispatch", "loop/loss_wait"})
SELF = "self"
#: the time between the close of the step before and this step's open
BETWEEN = "between_steps"
#: the loop's ``on_step`` hook: a window of the benchmark runs from one
#: step's hook to a later one's, so a record says on which side of it the
#: excess was (``hook_or_after``)
HOOK = "loop/hook"
MAIN_THREAD = "MainThread"
NATIVE_THREADS = "native threads"
PULSE_THREAD = "late-step-pulse"


# -- the cause table -------------------------------------------------------

def _half(r: Mapping[str, Any]) -> float:
    return r["excess_s"] / 2.0


def _pulse_missed(r: Mapping[str, Any]) -> bool:
    return r.get("pulse_missed_s", 0.0) >= _half(r)


def _pulse_beat(r: Mapping[str, Any]) -> bool:
    return "pulse_missed_s" in r and not _pulse_missed(r)


def _lock_wait_s(r: Mapping[str, Any]) -> float:
    return r.get("pulse_lock_waits", 0) * LOCK_WAIT_S


def _burned(r: Mapping[str, Any], main: bool) -> bool:
    """A Python thread (the loop's own if ``main``, else another) used the
    CPU for at least half the excess more than it usually does."""
    busiest = r.get("busiest_thread")
    if busiest is None or busiest == NATIVE_THREADS:
        return False
    return ((busiest == MAIN_THREAD) == main
            and r.get("busiest_thread_over_s", 0.0) >= _half(r))


#: (cause, what the numbers have to show); a late step's ``cause`` is the
#: first whose test holds. OBSERVABILITY.md has the same table in words.
CAUSES: Tuple[Tuple[str, str, Callable[[Mapping[str, Any]], bool]], ...] = (
    ("compile",
     "JAX traced, lowered or compiled a program inside the step for at "
     "least half the excess",
     lambda r: r.get("compiles", 0) > 0
     and r.get("compile_s", 0.0) >= _half(r)),
    ("model",
     "a step attribute that counts a slower lowering is above its median",
     lambda r: "slower_lowering" in r),
    ("gc",
     "the collector ran for at least half the excess",
     lambda r: r.get("gc_s", 0.0) >= _half(r)),
    ("machine_stopped",
     "the machine's own clock ticks fall short of the step's wall by at "
     "least half the excess: a paused or migrated virtual machine",
     lambda r: "machine_ran_s" in r
     and r["step_s"] - r["machine_ran_s"] >= max(_half(r), TICK_SLACK_S)),
    ("process_stopped",
     "the pulse missed at least half the excess, the machine ran, the "
     "process used no CPU to speak of and the pulse was not waking for "
     "the interpreter lock: throttled, frozen or stopped (throttled_s "
     "says which where the file exists; where the kernel counts no "
     "switches, pulse_lock_waits is absent and a native call that slept "
     "with the lock reads the same)",
     lambda r: _pulse_missed(r) and _lock_wait_s(r) < _half(r)
     and r.get("process_cpu_over_s", 0.0) < _half(r)),
    ("interpreter_held",
     "the pulse missed at least half the excess while a thread other than "
     "the loop's used the CPU, or while the pulse woke every 5 ms for an "
     "interpreter lock that a native call kept",
     lambda r: _pulse_missed(r) and not _burned(r, main=True)
     and (_burned(r, main=False) or _lock_wait_s(r) >= _half(r))),
    ("host",
     "the loop's own thread worked or waited on the host: the excess is in "
     "loop/batch_fetch, loop/hook, collab/* or the loop's self time (where "
     "names it; the pulse beat, or missed while the process used the CPU: "
     "a native call of the loop's own that kept the lock), or the main "
     "thread used the CPU",
     lambda r: _burned(r, main=True)
     or ("pulse_missed_s" in r and r["where"] not in DEVICE_WAIT)),
    ("device_or_runtime",
     "the pulse beat and the excess is in loop/grad_dispatch + "
     "loop/loss_wait: the host was alive and waiting",
     lambda r: _pulse_beat(r) and r["where"] in DEVICE_WAIT),
    ("unknown", "none of the above", lambda r: True),
)


def name_cause(record: Mapping[str, Any]) -> str:
    """The first cause of :data:`CAUSES` that ``record`` supports."""
    return next(name for name, _, holds in CAUSES if holds(record))


# -- what is read at a step's edges ----------------------------------------

def _own_switches() -> int:
    """Voluntary context switches of the calling thread so far (0 where
    the platform does not count by thread)."""
    who = getattr(resource, "RUSAGE_THREAD", None)
    return resource.getrusage(who).ru_nvcsw if who is not None else 0


class Pulse(threading.Thread):
    """The program's own heartbeat. Every :data:`BEAT_S` seconds it notes
    the time; a wake-up that comes late means that this thread did not get
    to run: the process or the machine was stopped, or another thread kept
    the interpreter lock. The two differ in what this thread did
    meanwhile: one that waits for the lock wakes every
    :data:`LOCK_WAIT_S` to ask for it, one that is not scheduled does not
    wake at all. It also takes a late step's stacks (the module's
    docstring) into ``stacks``, a text file. It touches nothing on the hot
    path: a step's edges tell it which step is open and read its sums."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 stacks=None):
        super().__init__(name=PULSE_THREAD, daemon=True)
        self._clock = clock
        self._stacks = stacks
        self._halt = threading.Event()
        self._lock = threading.Lock()
        self._missed_s = 0.0
        self._lock_waits = 0
        self._last = clock()
        #: the open step: (trace, when it opened, what the pulse had missed
        #: by then, the seconds of beating after which its stacks are taken)
        self._step: Optional[Tuple[str, float, float, float]] = None
        #: the innermost frames of the open step's threads, once taken
        self._frames: Optional[str] = None

    def run(self) -> None:
        last, switches = self._clock(), _own_switches()
        while not self._halt.wait(BEAT_S):
            now, seen = self._clock(), _own_switches()
            late = now - last - BEAT_S
            with self._lock:
                if late > BEAT_SLACK_S:
                    self._missed_s += late
                    # one switch is the beat's own wait
                    self._lock_waits += max(seen - switches - 1, 0)
                self._last = now
                step = self._stacks_due(now)
            last, switches = now, seen
            if step is not None:
                frames = self._take_stacks(step[0], now - step[1])
                with self._lock:
                    if self._step is step:
                        self._frames = frames

    def _stacks_due(self, now: float):
        """The open step, if it has been open for long enough in beats
        that came on time and its stacks are not taken yet."""
        step = self._step
        if step is None or self._frames is not None:
            return None
        beating = now - step[1] - (self._missed_s - step[2])
        return step if beating >= step[3] else None

    def _take_stacks(self, trace: str, open_s: float) -> str:
        """Every Python thread's stack but this one's into the file, most
        recent call first; returns their innermost frames. Under the
        interpreter lock: no frame moves meanwhile."""
        names = {t.ident: t.name for t in threading.enumerate()}
        text = [f"Late step ({trace} open {open_s:.3f} s):\n"]
        innermost = []
        # the loop's thread first: the record's line is cut to length
        for ident, frame in sorted(
                sys._current_frames().items(),
                key=lambda of: names.get(of[0]) != MAIN_THREAD):
            name = names.get(ident, f"thread {ident:#x}")
            if name == PULSE_THREAD:
                continue
            code = frame.f_code
            innermost.append(f"{name}: {os.path.basename(code.co_filename)}:"
                             f"{frame.f_lineno} {code.co_name}")
            text.append(f"Thread {name} (most recent call first):\n")
            while frame is not None:
                text.append(f'  File "{frame.f_code.co_filename}", line '
                            f"{frame.f_lineno} in {frame.f_code.co_name}\n")
                frame = frame.f_back
        if self._stacks is not None:
            try:
                self._stacks.write("".join(text))
                self._stacks.flush()
            except (OSError, ValueError):
                self._stacks = None
        return "; ".join(innermost)

    def opened(self, trace: str, stacks_after_s: float) -> None:
        with self._lock:
            self._step = (trace, self._clock(), self._missed_s,
                          stacks_after_s)
            self._frames = None

    def closed(self) -> Optional[str]:
        """No step is open; the innermost frames of the one that was, if
        its stacks were taken."""
        with self._lock:
            frames, self._step, self._frames = self._frames, None, None
            return frames

    def read(self) -> Tuple[float, int]:
        """(seconds its wake-ups came late, times it woke for the
        interpreter lock meanwhile), both since it started. A beat that
        is overdue *now* counts as far as it is: a stall that ends with
        its step is in that step's record without a wait for the pulse
        (which then adds the whole lateness, so the sum only grows)."""
        with self._lock:
            overdue = self._clock() - self._last - BEAT_S
            return (self._missed_s + (overdue if overdue > BEAT_SLACK_S
                                      else 0.0), self._lock_waits)

    def stop(self) -> None:
        self._halt.set()
        self.join()


def _open(path: Optional[str]):
    """``path`` opened once, to be read again at every step's edge with
    one ``pread`` (a sandbox's kernel charges tens of microseconds for an
    ``open``); None where it is absent or cannot be read."""
    if path is None:
        return None
    try:
        f = open(path, "rb", buffering=0)
        os.pread(f.fileno(), 1, 0)
        return f
    except OSError:
        return None


def _read(f) -> bytes:
    try:
        return os.pread(f.fileno(), 4096, 0)
    except OSError:
        return b""


#: the container's ``cpu.stat``: cgroup v2, then v1
_CPU_STAT = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
             "/sys/fs/cgroup/cpu,cpuacct/cpu.stat")


class HostCounters:
    """What the kernel has charged the process, its Python threads and the
    machine so far: one flat dict of numbers that only grow, and
    ``threads`` (name -> CPU seconds). The files are opened once, those
    this machine lacks are left out from then on; paths are arguments so
    that a test can point them at nothing."""

    def __init__(self, proc_stat: str = "/proc/stat",
                 pressure: str = "/proc/pressure",
                 cpu_stat: Optional[str] = "auto"):
        self._proc_stat = _open(proc_stat)
        opened = {what[:3]: _open(f"{pressure}/{what}")
                  for what in ("cpu", "io", "memory")}
        self._pressure = {what: f for what, f in opened.items() if f}
        self._cpu_stat = next(filter(None, map(
            _open, _CPU_STAT if cpu_stat == "auto" else (cpu_stat,))), None)
        self._ticks = os.sysconf("SC_CLK_TCK")
        self._cpus = os.cpu_count() or 1

    def leave_out(self, keys) -> None:
        """Stop reading the files that feed only ``keys``."""
        if "machine_ran_s" in keys:
            self._proc_stat = None

    def __call__(self) -> Dict[str, Any]:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        out: Dict[str, Any] = {
            "process_cpu_s": ru.ru_utime + ru.ru_stime,
            "vol_switches": ru.ru_nvcsw, "invol_switches": ru.ru_nivcsw,
            "major_faults": ru.ru_majflt, "threads": self._threads()}
        if self._proc_stat is not None:
            self._machine(out)
        for what, f in self._pressure.items():
            try:     # "some avg10=... total=<microseconds>"
                total = _read(f).split(b"\n", 1)[0].rsplit(b"total=", 1)[1]
                out[f"psi_{what}_s"] = int(total) / 1e6
            except (IndexError, ValueError):
                pass
        if self._cpu_stat is not None:
            self._throttled(out)
        return out

    @staticmethod
    def _threads() -> Dict[str, float]:
        cpu: Dict[str, float] = {}
        for t in threading.enumerate():
            try:
                seconds = time.clock_gettime(
                    time.pthread_getcpuclockid(t.ident))
            except (OSError, AttributeError, TypeError):
                continue        # it ended meanwhile, or no such clock here
            cpu[t.name] = cpu.get(t.name, 0.0) + seconds
        return cpu

    def _machine(self, out: Dict[str, Any]) -> None:
        text = _read(self._proc_stat)
        try:
            # user nice system idle iowait irq softirq steal; guest time
            # is inside user already. Their sum over the ticks a second
            # and the CPUs is the seconds the machine itself ran: a
            # virtual machine that was paused shows as ticks that are
            # missing
            fields = [int(x) for x in text.split(b"\n", 1)[0].split()[1:9]]
            out["steal_s"] = fields[7] / self._ticks
            out["machine_ran_s"] = sum(fields) / self._ticks / self._cpus
        except (IndexError, ValueError):
            pass

    def _throttled(self, out: Dict[str, Any]) -> None:
        for line in _read(self._cpu_stat).splitlines():
            key, _, value = line.partition(b" ")
            try:
                if key == b"throttled_usec":             # cgroup v2
                    out["throttled_s"] = int(value) / 1e6
                elif key == b"throttled_time":           # v1: nanoseconds
                    out["throttled_s"] = int(value) / 1e9
            except ValueError:
                pass


#: keys of ``memory_stats()`` -> the edge's names for them, whose
#: differences go into the record under the third name
_MEMORY = {"bytes_in_use": "mem_in_use", "num_allocs": "mem_allocs"}
_DELTA_NAME = {"mem_in_use": "mem_in_use_delta",
               "mem_allocs": "mem_allocs_delta"}

#: sources that a machine may have in name only: (the edge's key that a
#: live source must have moved, by how much in ``wall`` seconds, the
#: edge's keys that are left out with it from then on, like those of a
#: file that is absent). Judged once, over the steps before any can be
#: late. The kernel's event counters: the pulse alone makes fifty
#: voluntary switches a second, a sandbox's ``getrusage`` counts none but
#: a stray one (and no fault). ``/proc/stat``: a sandbox's stands still,
#: or counts other CPUs than ``os.cpu_count()`` says. The device's
#: allocator: a training step allocates, one that counts no allocation
#: keeps no statistics worth a call at every edge.
SOURCES: Tuple[Tuple[str, Callable[[float, float], bool],
                     Tuple[str, ...]], ...] = (
    ("vol_switches",
     lambda moved, wall: moved >= LIVE_SWITCHES_A_SECOND * wall,
     ("vol_switches", "invol_switches", "major_faults", "pulse_lock_waits")),
    ("machine_ran_s",
     lambda moved, wall: 0.9 * wall <= moved <= 1.1 * wall,
     ("machine_ran_s", "steal_s")),
    ("mem_allocs", lambda moved, wall: moved > 0,
     ("mem_allocs", "mem_in_use")),
)


def _median_of(history, key: str, name: str) -> float:
    return statistics.median(h[key].get(name, 0.0) for h in history)


class LateSteps:
    """The recorder. ``compiles`` is the process's compile counter,
    ``device_memory`` a callable that returns the fullest local device's
    ``memory_stats()`` (or None), ``slow_attributes`` the names of the
    step attributes that count a slower lowering (the model says them),
    ``stacks_path`` where the stacks are written (standard error if None),
    ``host`` the reader of the kernel's counters. A test injects ``host``,
    ``pulse`` and the tracer's clock."""

    def __init__(self, tracer: Tracer, compiles=None,
                 device_memory: Optional[Callable[[], Optional[dict]]] = None,
                 slow_attributes: Tuple[str, ...] = (),
                 stacks_path: Optional[str] = None,
                 host: Optional[Callable[[], Dict[str, Any]]] = None,
                 pulse: Optional[Callable[[], Tuple[float, int]]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.tracer = tracer
        self.compiles = compiles
        self.device_memory = device_memory
        self.slow_attributes = tuple(slow_attributes)
        self.stacks_path = stacks_path
        self._host = host if host is not None else HostCounters()
        self._read_pulse = pulse
        self._clock = clock
        self._pulse: Optional[Pulse] = None
        self._stacks_file = None
        self._history: deque = deque(maxlen=HISTORY)
        self._usual: Optional[float] = None
        #: when the last step's span ended, on the ring's clock
        self._ended: Optional[float] = None
        self._edge: Dict[str, Any] = {}
        #: when :meth:`start` read the first edge, and that edge; the
        #: edge's keys whose source is judged absent (None: not judged yet)
        self._started: Tuple[float, Dict[str, Any]] = (0.0, {})
        self._absent: Optional[frozenset] = None
        self._gc_t0 = 0.0
        self._gc_s = 0.0
        self._gc_n = 0
        self._warned_at: Optional[float] = None
        self._held: List[str] = []
        #: since :meth:`start`, for the line :meth:`stop` logs
        self._steps = 0
        self._excess_s = 0.0
        self._by_cause: Dict[str, int] = {}

    # -- lifetime (train_loop: start before the loop, stop in its finally) --

    def start(self) -> None:
        """Take the stacks' file and the collector's callback, start the
        pulse, and read the first edge."""
        try:
            self._stacks_file = (open(self.stacks_path, "a")
                                 if self.stacks_path is not None
                                 else sys.stderr)
        except OSError:
            self._stacks_file = None           # no stacks, never an error
        if self._read_pulse is None:
            self._pulse = Pulse(self._clock, self._stacks_file)
            self._pulse.start()
            self._read_pulse = self._pulse.read
        gc.callbacks.append(self._on_gc)
        self._steps, self._excess_s, self._by_cause = 0, 0.0, {}
        self._edge, self._ended = self._read_edge(), None
        self._started = (self._clock(), self._edge)

    def stop(self) -> None:
        """Give back what :meth:`start` took; say what is still held
        back, and the run's sum. Safe to call twice, and without
        :meth:`start`."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._pulse is not None:
            self._pulse.stop()
            self._pulse, self._read_pulse = None, None
        if self._stacks_file is not None:
            if self.stacks_path is not None:
                self._stacks_file.close()
            self._stacks_file = None
        if self._held:
            logger.warning("%d more late steps since the last line: %s",
                           len(self._held), self._take_held())
        if self._steps:
            logger.info(
                "late steps: %d of %d steps, +%.3f s in all%s",
                sum(self._by_cause.values()), self._steps, self._excess_s,
                "".join(f", {n} {cause}" for cause, n in sorted(
                    self._by_cause.items(), key=lambda kv: -kv[1])))
            self._steps = 0

    @contextlib.contextmanager
    def step(self, n: int):
        """``with late.step(n) as step_row:`` is the loop's n-th step: its
        ``loop/step`` span (what ``as`` binds), compared with the steps
        before it when it closes. A step that raised is no step to
        compare."""
        trace = f"step:{n}"
        mark = self.tracer.mark()
        if self._pulse is not None and len(self._history) >= MIN_CLOSED:
            self._pulse.opened(trace, max(STACKS_AFTER * self._usual,
                                          STACKS_FLOOR_S))
        compare = False
        try:
            with self.tracer.span(PLANE, STEP_PHASE, trace) as row:
                yield row
            compare = True
        finally:
            self._closed(trace, mark, compare)

    # -- the collector -----------------------------------------------------

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections do not nest and the sums only grow: the step's
        # close reads them and takes differences
        if phase == "start":
            self._gc_t0 = self._clock()
        else:
            self._gc_s += self._clock() - self._gc_t0
            self._gc_n += 1

    # -- a step's edge and its judgement -----------------------------------

    def _read_edge(self) -> Dict[str, Any]:
        absent = self._absent or ()
        edge = self._host()
        if self._read_pulse is not None:
            edge["pulse_missed_s"], edge["pulse_lock_waits"] = \
                self._read_pulse()
        edge["gc_s"], edge["gc_n"] = self._gc_s, self._gc_n
        if self.compiles is not None:
            edge["compiles"], edge["compile_s"] = self.compiles.cost()
        if self.device_memory is not None and "mem_allocs" not in absent:
            stats = self.device_memory() or {}
            for key, name in _MEMORY.items():
                if key in stats:
                    edge[name] = stats[key]
        for key in absent:
            edge.pop(key, None)
        return edge

    def _judge_sources(self, edge: Dict[str, Any]) -> None:
        """Once, when the first step that could be late is about to
        open: which of :data:`SOURCES` this machine has in name only."""
        at, first = self._started
        wall = self._clock() - at
        self._absent = frozenset(
            key for witness, live, keys in SOURCES
            if witness in first and witness in edge
            and not live(edge[witness] - first[witness], wall)
            for key in keys)
        for key in self._absent:
            edge.pop(key, None)
        if isinstance(self._host, HostCounters):
            self._host.leave_out(self._absent)
        if self._absent:
            logger.info("late steps: not counted on this machine, and left "
                        "out of the records: %s", ", ".join(sorted(
                            _DELTA_NAME.get(k, k) for k in self._absent)))

    def _closed(self, trace: str, mark: int, compare: bool) -> None:
        """The step's span has closed and its row is in the ring: keep
        its durations, read the edge, and if it ran over, say why."""
        frames = self._pulse.closed() if self._pulse is not None else None
        step = self._durations(trace, mark) if compare else None
        edge, before = self._read_edge(), self._edge
        self._edge = edge
        if step is not None:       # else the ring lost the row, or it raised
            self._keep(trace, step, edge, before, frames)
            if self._absent is None and len(self._history) >= MIN_CLOSED:
                self._judge_sources(edge)

    def _keep(self, trace: str, step: Dict[str, Any], edge: Dict[str, Any],
              before: Dict[str, Any], frames: Optional[str]) -> None:
        late = (len(self._history) >= MIN_CLOSED
                and step["comparable_s"] - self._usual
                > max(LATE_SHARE * self._usual, LATE_FLOOR_S))
        deltas = {_DELTA_NAME.get(k, k): edge[k] - before[k]
                  for k in edge if k != "threads" and k in before}
        threads = {name: cpu - before.get("threads", {}).get(name, 0.0)
                   for name, cpu in edge.get("threads", {}).items()}
        if "process_cpu_s" in deltas:
            threads[NATIVE_THREADS] = max(
                deltas["process_cpu_s"] - sum(threads.values()), 0.0)
        step["threads"] = threads
        step["process_cpu_s"] = deltas.get("process_cpu_s", 0.0)
        self._steps += 1
        if late:
            record = self._record(step, deltas, frames)
            self.tracer.add(PLANE, LATE_EVENT, trace, self._clock(), 0.0,
                            **record)
            self._warn(trace, record)
            self._excess_s += record["excess_s"]
            self._by_cause[record["cause"]] = self._by_cause.get(
                record["cause"], 0) + 1
        self._history.append(step)
        self._usual = statistics.median(h["comparable_s"]
                                        for h in self._history)

    def _durations(self, trace: str, mark: int) -> Optional[Dict[str, Any]]:
        """The step's rows, read back from the ring: its span, the
        seconds of each child phase, and its attributes, round work
        taken out of all of them."""
        whole, attrs, hook_at = None, {}, None
        phases: Dict[str, float] = {}      # direct children of the step
        starts: Dict[str, float] = {}      # when each first began
        inside: Dict[str, float] = {}      # round work, by the child it is in
        round_s = 0.0
        ended, self._ended = self._ended, None
        for row in self.tracer.since(mark):
            if row["trace"] != trace or row["plane"] != PLANE:
                continue           # another thread's: a round's hop spans
            phase, dur, parent = row["phase"], row["dur_s"], row.get("parent")
            if phase == STEP_PHASE:
                whole, attrs = dur, row.get("a", {})
                self._ended = row["t0"] + dur
                if ended is not None:
                    # what lies between two steps' spans (this recorder's
                    # edge, the loop's condition) belongs to the later one,
                    # as it does to the harness's interval
                    phases[BETWEEN] = max(row["t0"] - ended, 0.0)
            elif phase in ROUND_WORK:
                round_s += dur
                inside[parent] = inside.get(parent, 0.0) + dur
            elif parent == STEP_PHASE:
                if phase == HOOK:
                    hook_at = row["t0"]
                if dur > 0:
                    phases[phase] = phases.get(phase, 0.0) + dur
                    starts.setdefault(phase, row["t0"])
        if whole is None:
            return None
        phases = {p: s - inside.get(p, 0.0) for p, s in phases.items()}
        comparable = whole - round_s + phases.get(BETWEEN, 0.0)
        phases[SELF] = comparable - sum(phases.values())
        return {"step_s": whole, "round_s": round_s,
                "comparable_s": comparable, "phases": phases,
                #: the phases that began with the hook or after it; the
                #: loop's self time is counted among them
                "hook_or_after": {SELF} | {
                    p for p, t0 in starts.items()
                    if hook_at is not None and t0 >= hook_at},
                "attrs": {k: v for k, v in attrs.items()
                          if isinstance(v, (int, float))}}

    def _record(self, step: Dict[str, Any], deltas: Dict[str, Any],
                frames: Optional[str]) -> Dict[str, Any]:
        history, usual = self._history, self._usual
        excess = step["comparable_s"] - usual
        over = {p: s - _median_of(history, "phases", p)
                for p, s in step["phases"].items()}
        where = max(over, key=over.get)
        record: Dict[str, Any] = {
            "step_s": step["step_s"], "usual_s": usual, "excess_s": excess,
            "where": where, "where_excess_s": over[where],
            "hook_or_after": int(where in step["hook_or_after"])}
        if step["round_s"]:
            record["round_s"] = step["round_s"]
        if step["phases"].get(BETWEEN):
            record["between_s"] = step["phases"][BETWEEN]
        record.update(deltas)
        if "process_cpu_s" in deltas:
            record["process_cpu_over_s"] = deltas["process_cpu_s"] \
                - statistics.median(h["process_cpu_s"] for h in history)
        thread_over = {name: cpu - _median_of(history, "threads", name)
                       for name, cpu in step["threads"].items()
                       if name != PULSE_THREAD}
        if thread_over:
            busiest = max(thread_over, key=thread_over.get)
            record.update(busiest_thread=busiest,
                          busiest_thread_cpu_s=step["threads"][busiest],
                          busiest_thread_over_s=thread_over[busiest])
        for name, value in step["attrs"].items():
            seen = [h["attrs"].get(name, 0.0) for h in history]
            median = statistics.median(seen)
            if not min(seen) <= value <= max(seen):
                record[name] = value
                record[f"{name}_usual"] = median
            if name in self.slow_attributes and value > median:
                record["slower_lowering"] = name
        if frames is not None:
            record["stacks"] = (f"{self.stacks_path or 'standard error'}: "
                                f"{frames}")[:480]
        record["cause"] = name_cause(record)
        return {k: round(v, 6) if isinstance(v, float) else v
                for k, v in record.items()}

    # -- the log -----------------------------------------------------------

    def _warn(self, trace: str, r: Dict[str, Any]) -> None:
        now = self._clock()
        if (self._warned_at is not None
                and now - self._warned_at < WARN_EVERY_S):
            self._held.append(f"{trace} +{r['excess_s']:.3f} s in "
                              f"{r['where']}: {r['cause']}")
            return
        self._warned_at = now
        held = (f" ({len(self._held)} more held back since the last line: "
                f"{self._take_held()})" if self._held else "")
        logger.warning("%s%s", late_step_line(trace, r), held)

    def _take_held(self) -> str:
        """The late steps held back, in words (the first :data:`HELD_SHOWN`
        of them; the ring has every one), and none held any more."""
        held, self._held = self._held, []
        more = len(held) - HELD_SHOWN
        return "; ".join(held[:HELD_SHOWN]) + (
            f"; and {more} more" if more > 0 else "")


def late_step_line(trace: str, r: Mapping[str, Any]) -> str:
    """One late step in words: the WARNING's text, and what
    ``scripts/trace_report.py`` prints for a ``loop/late_step`` row."""
    said = [f"{what} {r[key]:.2f} s" for key, what in (
        ("pulse_missed_s", "pulse missed"), ("process_cpu_s", "process CPU"),
        ("machine_ran_s", "machine ran"), ("throttled_s", "throttled"))
        if key in r]
    said += [f"{what} {r[key]:.2f} s" for key, what in (
        ("steal_s", "stolen"), ("gc_s", "collector"),
        ("compile_s", "compiling")) if r.get(key)]
    if r.get("pulse_lock_waits", 0) > 1:
        said.append(f"pulse woke {r['pulse_lock_waits']} times for the "
                    "interpreter lock")
    elif "pulse_lock_waits" not in r and r["cause"] == "process_stopped":
        said.append("switches are not counted here (a native call that "
                    "slept with the interpreter lock reads the same)")
    if "busiest_thread" in r:
        said.append(f"{r['busiest_thread']} used "
                    f"{r['busiest_thread_cpu_s']:.2f} s of CPU")
    if "invol_switches" in r:
        said.append(f"{r['invol_switches']} involuntary / "
                    f"{r.get('vol_switches', 0)} voluntary switches")
    if "slower_lowering" in r:
        name = r["slower_lowering"]
        said.append(f"{name} {r.get(name)} where "
                    f"{r.get(name + '_usual', 0.0)}")
    line = (f"{trace} took {r['step_s']:.3f} s where {r['usual_s']:.3f} is "
            f"usual (+{r['excess_s']:.3f} s in {r['where']}): "
            f"{r['cause']}: " + ", ".join(said))
    if "stacks" in r:
        line += f"; stacks in {r['stacks']}"
    return line
