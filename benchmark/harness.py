"""One cell, once: set up the trainer peer as ``run_trainer`` would, check it
against the plain reference, then measure a window of the production loop.

From the program this takes only the system under test
(``cli.run_trainer``'s parser and ``configs_from_args``, ``TrainingTask``,
``training.loop.train_loop`` with its ``on_step`` hook) and its kernel
names; everything that measures or
judges lives in ``benchmark/``.

Phases, all but the last counted as set-up:

1. native DHT library, compile cache (every program, the eager init's small
   ones included: minimum compile time 0);
2. ``TrainingTask`` and its sharded ``train_state`` from ``--seed``
   (``init_s``);
3. Mosaic census of the lowered grad step, held to the roles the
   configuration lists (``mosaic_census``: a layer that gave way to XLA is
   ``correct: false``; how a role is split into kernels is the program's);
4. reference check: the system's real grad step on a batch that tiles two
   seeded sequences, against the reference of the configuration's
   yardstick (``cell.yardstick``, ``benchmark/yardsticks/<name>.py``) on
   those two (``reference_check_s``);
5. ``train_loop``: one warm-up batch, then the loop's first steps until the
   window is armed;
6. the window: from one ``on_step`` boundary to the first boundary at or
   past ``--seconds`` later; the rate is all of its steps over all of its
   wall. Inside it the harness only stamps ``time.perf_counter()``; with
   ``--trace 1`` it also wraps four calls in host spans, runs a heartbeat
   thread beside the loop (``Watch``), and profiles the three steps that
   follow the window.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import logging
import math
import os
import re
import resource
import shutil
import statistics
import sys
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

from benchmark import intervals
from benchmark.manifest import BenchFailure, Cell, reducer

TRACED_STEPS = 3
SEED_MODULUS = 2 ** 31 - 1       # the driver's seeds pass 32 signed bits
PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of this kind; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add a row with its source")
    return table[device_kind]


class _WindowOver(Exception):
    """Raised from ``on_step`` to leave ``train_loop`` when the last window
    has closed."""


def kernel_census(lowered_text: str) -> collections.Counter:
    """Mosaic custom calls in a lowered program, counted by kernel name
    (copy of ``chip_smoke.kernel_census``)."""
    return collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def mosaic_census(lowered_text: str, roles: List[str]) -> Dict[str, Any]:
    """A lowered program's Mosaic kernels held to a configuration's
    ``mosaic_kernels``. Each entry names a **role** as a regular expression
    that is ``re.fullmatch``ed against the kernel names (a plain name is its
    own expression): ``missing`` are the roles no kernel fills, which fail
    ``correct``; ``unlisted`` the kernels no role names, with their counts,
    which are printed and fail nothing. The one place that matches: the
    harness and the tests under ``tests/benchmark_tests/`` both call it."""
    found = kernel_census(lowered_text)
    fills = {role: [name for name in found if re.fullmatch(role, name)]
             for role in roles}
    named = {name for names in fills.values() for name in names}
    return {"found": dict(found),
            "missing": [role for role in roles if not fills[role]],
            "unlisted": {name: n for name, n in found.items()
                         if name not in named}}


class CompileLog:
    """``jax.monitoring`` sink (after ``chip_smoke._CompileLog``): seconds
    in the backend compiler or the cache load per jitted program, seconds
    tracing and lowering, persistent-cache hits and misses — each stamped,
    so that what falls inside a window can be counted."""

    def __init__(self):
        self.compiles: List[tuple] = []      # (stamp, program, seconds)
        self.trace_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.compiles.append(
                (time.perf_counter(), str(kw.get("fun_name", "?")), seconds))
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            self.trace_s += seconds

    def on_event(self, event: str, **kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1

    def between(self, lo: float, hi: float) -> List[tuple]:
        return [c for c in self.compiles if lo <= c[0] <= hi]


class _Memory(logging.Handler):
    """WARNING records kept in memory; written out after the window."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.records: List[tuple] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append((time.perf_counter(), record.name,
                             record.levelname, record.getMessage()))


def host_counters() -> Dict[str, float]:
    """What the kernel has charged this process and its machine so far. Read
    at a window's two edges, outside it, so that a stall can be set against
    them afterwards: seconds stolen from the machine by its hypervisor,
    times this process was taken off a core, page faults served from disk."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {"process_cpu_s": ru.ru_utime + ru.ru_stime,
           "involuntary_switches": ru.ru_nivcsw,
           "major_faults": ru.ru_majflt}
    try:
        cpu = Path("/proc/stat").read_text().split("\n", 1)[0].split()
        out["steal_s"] = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    return out


class Window:
    """The step clock. ``on_step`` is ``train_loop``'s hook: it fires after
    ``float(metrics["loss"])``, i.e. when the device has finished the
    step."""

    def __init__(self, seconds: float, repeat: int, setup_steps: int,
                 trace_dir: Optional[Path]):
        self.seconds = seconds
        self.repeat = repeat
        self.setup_steps = setup_steps
        self.trace_dir = trace_dir
        self.armed = False
        self.windows: List[Dict[str, Any]] = []   # closed windows
        self.stamps: List[float] = []
        self.losses: List[float] = []
        self.traced_steps = 0
        self._traced_left = 0
        self._window_span = None
        self._host: Dict[str, float] = {}

    def on_step(self, n: int, loss: float) -> None:
        now = time.perf_counter()
        if self._window_span is not None:          # the traced steps
            self._traced_left -= 1
            if self._traced_left == 0:
                self._stop_trace()
                raise _WindowOver()
            return
        if not self.stamps:
            if self.armed:
                self._host = host_counters()
                self.stamps.append(time.perf_counter())  # the window opens
            elif n >= self.setup_steps:
                # the collector's last run before the window: what is
                # alive now is frozen out of later collections
                gc.collect()
                gc.freeze()
                self.armed = True
            return
        self.stamps.append(now)
        self.losses.append(loss)
        if now - self.stamps[0] >= self.seconds:
            host = host_counters()
            self.windows.append({
                "stamps": self.stamps, "losses": self.losses,
                "host": {k: host[k] - self._host[k] for k in host}})
            if len(self.windows) < self.repeat:
                self._host = host
                self.stamps, self.losses = [time.perf_counter()], []
            elif self.trace_dir is not None:
                self._start_trace()
            else:
                raise _WindowOver()

    def _start_trace(self) -> None:
        """The window has closed: profile the next TRACED_STEPS steps of the
        same loop. Starting and stopping the profiler takes seconds, so it
        stays outside the window, and the ``bench/traced_window`` span opens
        only once the profiler runs."""
        import jax
        # host TraceAnnotations and device operations only: the Python
        # tracer would slow every call of the traced steps
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=options)
        self._traced_left = TRACED_STEPS
        self._window_span = jax.profiler.TraceAnnotation(
            "bench/traced_window")
        self._window_span.__enter__()

    def _stop_trace(self) -> None:
        import jax
        self._window_span.__exit__(None, None, None)
        self._window_span = None
        jax.profiler.stop_trace()
        self.traced_steps = TRACED_STEPS

    def abort_trace(self) -> None:
        if self._window_span is not None:   # the loop died while tracing
            self._stop_trace()
            self.traced_steps = 0


class Watch(threading.Thread):
    """Traced runs only: a heartbeat beside the loop, so that a stall in a
    window can be told apart afterwards. Every BEAT seconds it notes the
    time. A beat that comes FREEZE late means that this process, or its
    whole machine, was not running: no line of the program can be charged
    with that. When the loop's step is LATE times the median so far and the
    heart still beats, it takes the main thread's stack and open span,
    once a step: that names what the program was doing."""

    BEAT, FREEZE, LATE = 0.02, 0.25, 1.25

    def __init__(self, window: Window, spans: "Spans"):
        super().__init__(name="bench-watch", daemon=True)
        self.window, self.spans = window, spans
        self.freezes: List[tuple] = []       # (stamp, seconds not running)
        self.late_steps: List[Dict[str, Any]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        main = threading.main_thread().ident
        last, seen = time.perf_counter(), None
        while not self._halt.wait(self.BEAT):
            now = time.perf_counter()
            if now - last - self.BEAT > self.FREEZE:
                self.freezes.append((last, now - last))
            last = now
            stamps = self.window.stamps
            if len(stamps) < 4 or stamps[-1] == seen:
                continue
            usual = statistics.median(intervals.intervals_of(stamps))
            if now - stamps[-1] > self.LATE * usual:
                seen = stamps[-1]
                frame = sys._current_frames().get(main)
                self.late_steps.append({
                    "t": now, "step_open_s": now - seen,
                    "span": self.spans.open,
                    "stack": traceback.format_stack(frame)[-6:]
                    if frame else []})

    def stop(self) -> None:
        self._halt.set()
        self.join()

    def inside(self, lo: float, hi: float) -> Dict[str, Any]:
        return {"freezes": [(t - lo, s) for t, s in self.freezes
                            if lo <= t <= hi],
                "late_steps": [dict(r, t=r["t"] - lo)
                               for r in self.late_steps if lo <= r["t"] <= hi]}


class Spans:
    """Host spans around the loop's four calls, recorded on the host clock
    and written into the profiler's trace (``TraceAnnotation``) so that an
    idle gap can be named. Installed only in a traced run: on the task's
    own attributes, no file of the program changes."""

    NAMES = ("bench/batch_fetch", "bench/grad_step_dispatch",
             "bench/grad_step_wait", "bench/collab_step")

    def __init__(self):
        self.rows: Dict[str, List[tuple]] = {n: [] for n in self.NAMES}
        self.open: Optional[str] = None      # read by ``Watch``

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        self.open = name
        with jax.profiler.TraceAnnotation(name):
            yield
        self.open = None
        self.rows[name].append((t0, time.perf_counter() - t0))

    def install(self, task) -> None:
        import jax
        grad_step, batches = task.grad_step, task.batches
        collab_step = task.collab_optimizer.step

        def timed_grad_step(params, batch):
            with self.span("bench/grad_step_dispatch"):
                out = grad_step(params, batch)
            with self.span("bench/grad_step_wait"):
                jax.block_until_ready(out[1]["loss"])
            return out

        def timed_batches():
            it = batches()
            while True:
                with self.span("bench/batch_fetch"):
                    batch = next(it)
                yield batch

        def timed_collab_step(grads, batch_size):
            with self.span("bench/collab_step"):
                return collab_step(grads, batch_size=batch_size)

        task.__dict__["grad_step"] = timed_grad_step
        task.batches = timed_batches
        task.collab_optimizer.step = timed_collab_step

    def inside(self, lo: float, hi: float) -> Dict[str, List[float]]:
        return {n: [d for t, d in rows if lo <= t <= hi]
                for n, rows in self.rows.items()}


class RunContext:
    """What the per-layer readers (``benchmark/reducers``) may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# -- set-up pieces ----------------------------------------------------------

def trainer_argv(cell: Cell, seed: int) -> List[str]:
    """The ``run_trainer`` command line of this cell: all of it data, from
    the configuration and traffic files."""
    t = cell.traffic
    return ["--preset", cell.config["preset"],
            "--per-device-batch", str(t["per_device_batch"]),
            "--grad-accum-steps", str(t["grad_accum_steps"]),
            "--target-batch-size", str(t["target_batch_size"]),
            "--seed", str(seed % SEED_MODULUS),
            "--warmup-batches", str(t.get("warmup_batches", 1)),
            "--log-level", "WARNING",
            *(str(a) for a in t.get("trainer_args", []))]


def check_model(task, cell: Cell) -> None:
    """The configuration file holds the configuration as it is run."""
    import dataclasses
    ran = dataclasses.asdict(task.model_cfg)
    for key, want in cell.config["model"].items():
        have = ran[key]
        have = list(have) if isinstance(have, tuple) else have
        if have != want:
            raise BenchFailure(
                f"configuration {cell.config_name}: {key} is {have!r} in the "
                f"program's preset and {want!r} in the file")


def census_check(task, cell: Cell, batch) -> Dict[str, Any]:
    """Lower the task's jitted grad step on the operands the loop feeds it
    and count the Mosaic kernels (``chip_smoke.lowered_steps`` with the real
    operands, so that trace and lowering are shared with the loop's own
    call). Its compiled plan says how much HBM the step's temporaries take:
    set against the allocator's reservation, it tells whether the system's
    step or another program holds the largest one."""
    lowered = task.grad_step.lower(task.train_state.params, batch)
    # a cache load: the loop's own call compiled the same module
    plan = lowered.compile().memory_analysis()
    return dict(mosaic_census(lowered.as_text(),
                              cell.config["mosaic_kernels"]),
                grad_step_plan_bytes=int(
                    getattr(plan, "temp_size_in_bytes", 0) or 0))


def engagement_records() -> Dict[str, str]:
    """What the program's mechanisms said of themselves when the step was
    traced: every attribute of the ``setup/warmup`` ring row that is a
    sentence (read as ``reducers/program_attr.py`` reads a row's; its
    counts, such as ``steps``, are left out), whatever the program names
    them, so that a mechanism a later PR adds is printed with no edit here.
    Printed beside the census; no metric reads them. Empty where the
    program keeps no ring."""
    from benchmark.reducers import program_span
    said: Dict[str, str] = {}
    for row in program_span.ring_rows() or []:
        if (row.get("plane"), row.get("phase")) == (program_span.PLANE,
                                                    "setup/warmup"):
            said.update({k: v for k, v in row.get("a", {}).items()
                         if isinstance(v, str)})
    return said


def reference_check(task, cell: Cell, seed: int) -> Dict[str, Any]:
    """The system's loss and gradients, through its real jitted grad step
    on a batch that tiles two seeded sequences, against the plain
    reference of the configuration's yardstick on those two sequences. The
    mean over the tiled batch is the mean over the two, so no second
    program is compiled for the check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.parallel.mesh import batch_sharding

    model = cell.config["model"]
    tol = cell.config["tolerance"]
    rng = np.random.default_rng(seed % SEED_MODULUS)
    n = task.local_batch_size
    text2 = rng.integers(2, model["vocab_text"],
                         (2, model["text_seq_len"]), dtype=np.int32)
    image2 = rng.integers(0, model["vocab_image"],
                          (2, model["image_grid"] ** 2), dtype=np.int32)
    batch = jax.device_put(
        {"text": np.tile(text2, (n // 2, 1)),
         "image": np.tile(image2, (n // 2, 1))}, batch_sharding(task.mesh))
    params = task.train_state.params
    grads, metrics = task.grad_step(params, batch)
    loss = float(metrics["loss"])

    dev = jax.devices()[0]
    on_dev = lambda tree: jax.tree.map(
        lambda a: jax.device_put(np.asarray(a) if len(a.devices()) > 1
                                 else a, dev), tree)
    ref_loss, ref_grads = cell.yardstick.loss_and_grads(
        on_dev(params), jnp.asarray(text2), jnp.asarray(image2), model,
        checkpoint_blocks=True)
    ref_loss = float(ref_loss)

    def rel_l2(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

    errs = {jax.tree_util.keystr(k): rel_l2(g, r) for (k, g), r in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree.leaves(ref_grads))}
    dtypes = {str(a.dtype) for a in jax.tree.leaves(params)}
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    worst = max(errs, key=errs.get)
    ok = (math.isfinite(loss) and loss_err <= tol["loss_rel"]
          and errs[worst] <= tol["grad_rel_l2"]
          and dtypes == {model["param_dtype"]})
    return {"ok": ok, "loss": loss, "reference_loss": ref_loss,
            "loss_rel_err": loss_err, "grad_rel_l2_max": errs[worst],
            "grad_rel_l2_worst_leaf": worst,
            "grad_rel_l2_median": statistics.median(errs.values()),
            "param_dtypes": sorted(dtypes), "tolerance": tol,
            "first_batch": batch}


# -- the run ------------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             out_dir: Path, t_start: float, repeat: int = 1,
             require_backend: Optional[str] = "tpu",
             interpret_kernels: bool = False, say=print) -> Dict[str, Any]:
    """Run one cell once and return the result object of the last line.
    ``require_backend=None`` and ``interpret_kernels`` are the test-only
    hook for the CPU rehearsal (parameters of this function, not options
    of the command); a rehearsal's numbers are never printed as a result.
    """
    out_dir.mkdir(parents=True, exist_ok=True)
    setup: Dict[str, float] = {}

    def mark(name: str, since: float) -> float:
        now = time.perf_counter()
        setup[name] = now - since
        return now

    # -- 1: before this process touches JAX -------------------------------
    t = time.perf_counter()
    from dalle_tpu.swarm import _native
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    _native.load()   # built on first use: its seconds are named here
    cache_dir = enable_compile_cache()
    memory_log = _Memory()
    logging.getLogger().addHandler(memory_log)
    logging.getLogger().setLevel(logging.WARNING)

    with contextlib.ExitStack() as stack:
        stack.callback(logging.getLogger().removeHandler, memory_log)

        import jax
        # every compile of a cached run is a cache load, the eager init's
        # hundreds of small programs included (JAX's defaults skip what
        # compiled in under 1 s)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        backend = jax.default_backend()
        devices = jax.devices()
        if require_backend is not None and backend != require_backend:
            raise BenchFailure(f"jax.default_backend() is {backend!r}, this "
                               f"benchmark measures {require_backend!r} only")
        if len(devices) != cell.chips:
            raise BenchFailure(f"{cell.name} asks for {cell.chips} chip(s), "
                               f"JAX finds {len(devices)}")
        kind = devices[0].device_kind
        peaks = peaks_for(kind) if require_backend else \
            {"bf16_flops_per_s": float("nan"),
             "hbm_bytes_per_s": float("nan")}
        compiles = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            compiles.on_duration)
        jax.monitoring.register_event_listener(compiles.on_event)
        t = mark("imports_native_s", t)

        from dalle_tpu.cli import run_trainer
        from dalle_tpu.models import attention
        from dalle_tpu.task import TrainingTask
        from dalle_tpu.training.loop import train_loop
        stack.callback(setattr, attention, "_PALLAS_INTERPRET",
                       attention._PALLAS_INTERPRET)
        attention._PALLAS_INTERPRET = interpret_kernels

        # -- 2: the task and its state, from the seed ---------------------
        args = run_trainer.build_parser().parse_args(trainer_argv(cell, seed))
        task = TrainingTask(*run_trainer.configs_from_args(args))
        check_model(task, cell)
        jax.block_until_ready(task.train_state)
        t = mark("init_s", t)
        local_batch = task.local_batch_size
        tokens_per_step = local_batch * cell.yardstick.tokens_per_sample(
            cell.config["model"])

        # -- 4 (and 3): reference check, then the census -------------------
        ref = reference_check(task, cell, seed)
        first_batch = ref.pop("first_batch")
        t = mark("reference_check_s", t)
        census = census_check(task, cell, first_batch)
        del first_batch
        t = mark("census_s", t)
        correct = bool(ref["ok"] and not census["missing"])
        # each number that decided it, beside its limit
        compared = {
            "loss_rel": [ref["loss_rel_err"], ref["tolerance"]["loss_rel"]],
            "grad_rel_l2": [ref["grad_rel_l2_max"],
                            ref["tolerance"]["grad_rel_l2"]],
            "census_missing": [len(census["missing"]), 0]}

        # -- 5, 6: the production loop --------------------------------------
        trace_dir = out_dir / "trace" if trace else None
        window = Window(seconds, repeat, cell.traffic.get("setup_steps", 2),
                        trace_dir)
        spans = Spans() if trace else None
        watch = Watch(window, spans) if trace else None
        t_loop = time.perf_counter()
        with task:
            if trace:
                spans.install(task)
                watch.start()
                stack.callback(watch.stop)
            try:
                train_loop(task, warmup_steps=args.warmup_batches,
                           on_step=window.on_step)
            except _WindowOver:
                pass
            finally:
                window.abort_trace()
            t_end = time.perf_counter()
            stats = [d.memory_stats() or {} for d in devices]
        # The allocator counts buffers (`peak_bytes_in_use`) and, apart from
        # them, what it reserves for a loaded program's temporaries
        # (`peak_bytes_reserved`; benchmark/probes/allocator_temporaries.py,
        # PERF.md section 6). A chip holds both at once.
        fullest = max(stats, key=lambda m: m.get("peak_bytes_in_use", 0)
                      + m.get("peak_bytes_reserved", 0))
        buffers_peak = fullest.get("peak_bytes_in_use", 0)
        reserved_peak = fullest.get("peak_bytes_reserved", 0)
        peak_bytes = buffers_peak + reserved_peak

    # -- after the window: reduce, write, report ---------------------------
    if not window.windows:
        raise BenchFailure("the loop ended before a window closed")
    first_open = window.windows[0]["stamps"][0]
    setup["loop_to_window_s"] = first_open - t_loop
    setup_s = first_open - t_start
    setup_compiles = compiles.between(0.0, first_open)
    compile_by_program: Dict[str, float] = collections.defaultdict(float)
    for _, program, secs in setup_compiles:
        compile_by_program[program] += secs

    reduced = None
    t_trace = time.perf_counter()
    if trace:
        from benchmark import trace as trace_mod
        raw = trace_mod.load_xplane(trace_mod.find_xplane(trace_dir))
        (out_dir / "trace_lines.json").write_text(json.dumps(
            trace_mod.digest(raw), indent=0))
        try:
            reduced = trace_mod.Reduced(raw)
        except ValueError as e:   # no operation ran on the device
            if require_backend is not None:
                raise BenchFailure(str(e)) from e
        shutil.rmtree(trace_dir, ignore_errors=True)   # the digest stays
    trace_read_s = time.perf_counter() - t_trace

    results = []
    for w in window.windows:
        stamps = w["stamps"]
        summary = intervals.summarize(stamps, tokens_per_step, cell.chips)
        summary.update(
            window_compiles=len(compiles.between(stamps[0], stamps[-1])),
            attempted=len(w["losses"]),
            failed=sum(1 for x in w["losses"] if not math.isfinite(x)),
            host=w["host"], intervals_s=intervals.intervals_of(stamps))
        if watch is not None:
            watched = watch.inside(stamps[0], stamps[-1])
            summary.update(watched, host_freeze_s=sum(
                s for _, s in watched["freezes"]))
        results.append(summary)
    last, last_stamps = results[-1], window.windows[-1]["stamps"]

    values = dict(last, setup_s=setup_s, **setup,
                  compile_s=sum(s for _, _, s in setup_compiles),
                  buffers_peak_bytes=buffers_peak,
                  program_reserved_bytes=reserved_peak,
                  grad_step_plan_bytes=census["grad_step_plan_bytes"])
    ctx = RunContext(
        model=cell.config["model"], yardstick=cell.yardstick,
        chips=cell.chips, peaks=peaks, values=values,
        spans=spans.inside(last_stamps[0], last_stamps[-1]) if spans else {},
        trace=reduced, traced_steps=window.traced_steps,
        samples_per_step=local_batch)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            value = reducer(m["reducer"])(ctx, **m.get("params", {}))
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": last["attempted"],
        "failed": last["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["compared"] = compared          # last in the line

    # earlier lines, for the record: they decide nothing
    say(json.dumps({"setup": {k: round(v, 3) for k, v in setup.items()},
                    "setup_s": round(setup_s, 3),
                    "compile_s": round(values["compile_s"], 3),
                    "trace_lower_s": round(compiles.trace_s, 3),
                    "setup_compiles": len(setup_compiles),
                    "cache_hits": compiles.cache_hits,
                    "cache_misses": compiles.cache_misses,
                    "cache_dir": cache_dir,
                    "memory_stats_after_window": fullest,
                    "shutdown_s": round(t_end - last_stamps[-1], 3),
                    # reading and reducing the trace file, after the window
                    "trace_read_s": round(trace_read_s, 3)}))
    say(json.dumps({"reference_check": ref, "census": census,
                    "engagement": engagement_records()}))
    for i, r in enumerate(results):
        say(json.dumps({"window": i, **r}))
    slow = sorted(compile_by_program.items(), key=lambda kv: -kv[1])[:8]
    say(json.dumps({"slowest_compiles_or_loads": slow}))
    (out_dir / "intervals.json").write_text(json.dumps(
        {"workload": cell.name, "seed": seed, "windows": results,
         "setup": setup, "spans": spans.rows if spans else {},
         "compiles": compiles.compiles}, indent=1))
    (out_dir / "warnings.log").write_text(
        "".join(f"{t - t_start:9.3f} {name} {lvl} {msg}\n"
                for t, name, lvl, msg in memory_log.records))
    if reduced is not None:
        (out_dir / "device_ops.json").write_text(json.dumps(
            sorted(reduced.seconds_by_name().items(),
                   key=lambda kv: -kv[1]), indent=0))
        # the same by scope path: what a ``scope`` expression is written from
        (out_dir / "device_scopes.json").write_text(json.dumps(
            reduced.seconds_by_name_and_scope()[:400], indent=0))
    return result
