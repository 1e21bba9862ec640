"""Reduction of a profiler trace to device busy/idle time, time by
operation name, and idle gaps named by the host span they fell in.

Works on a neutral form, so that a small recorded trace can be checked in
as a JSON fixture and every PR computes the same numbers the same way::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

A device event may carry a fourth element, the **scope path** under which
the program issued the operation (``[name, start_ns, duration_ns, scope]``,
``""`` where there is none; an event of three elements has none).
``load_xplane`` turns the ``.xplane.pb`` file JAX's profiler writes into
that form with ``benchmark/xspace.py``, a reader of the file's wire format:
the path is the ``tf_op`` stat of the event's *metadata*, which
``jax.profiler.ProfileData`` does not hand out.
The path is JAX's name stack, as the flagship's grad step wrote it on the
chip (PR 27; a trailing ``:`` is the empty type of ``name:type``)::

    jit(grad_step)/while/body/closed_call/jvp(DALLE)/DALLE.backbone/transformer/while/body/closed_call/cycle/block_0/ff/dot_general:
    jit(grad_step)/while/body/closed_call/transpose(jvp(DALLE))/DALLE.backbone/transformer/while/body/closed_call/cycle/cycle/checkpoint/rematted_computation/block_0/attn/v/dot_general:
    jit(grad_step)/while/body/closed_call/grad_accumulate/add:

The first ``while`` is the accumulation scan, ``jvp(DALLE)`` the forward
and ``transpose(jvp(DALLE))`` the backward pass, ``transformer/while`` the
layer scan, ``checkpoint/rematted_computation`` a block computed again;
``block_0/ff``, ``attn/v``, ``head``, ``ce``, ``embed``, ``grad_accumulate``
are the program's flax modules and ``jax.named_scope``s. A fusion carries
the path of the one operation XLA named it after, so what was fused into a
matmul's fusion (a residual's store, a LayerNorm reduction) counts with
that matmul's module. ``Reduced.seconds_matching`` and its siblings take a
``scope`` expression, searched in the path (``""`` where there is none).

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. The profiler names an event by the whole HLO
instruction; ``op_key`` cuts that to the instruction's name without its
number (``convolution_add_fusion``) and marks a Mosaic kernel, which XLA
names after the flax module that called it, as ``attn[mosaic]``,
``ff[mosaic]``, ``attn_norm[mosaic]``. Control-flow operations (``while``, ``call``,
``conditional``) enclose the operations of their bodies on the same line;
an enclosing event is charged only the time none of its children cover,
so a scan's ``while`` does not count its body twice. The device is busy
where a leaf operation (one that encloses nothing) runs.
Host spans are the ``bench/...`` ``TraceAnnotation`` events the harness
writes on the host plane; they share the trace's clock.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import (Any, Callable, Dict, Iterable, List, Mapping, Optional,
                    Sequence, Tuple)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/traced_window"

MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(r"^%?([^\s=(]+?)(?:\.\d+)*(?=\s|=|$)")

SCOPE_STAT = "tf_op"

Event = Tuple[Any, ...]               # name, start_ns, duration_ns[, scope]
Interval = Tuple[int, int]            # start_ns, end_ns


def op_key(name: str) -> str:
    """The short name of a device operation (see the module docstring)."""
    m = _INSTRUCTION.match(name)
    key = m.group(1) if m else name[:64]
    return key + "[mosaic]" if MOSAIC_TARGET in name else key


def load_xplane(path: Path) -> Dict[str, Any]:
    from benchmark import xspace

    def want_line(plane: str, line: str) -> bool:
        # of a device plane only its operations
        return line == OP_LINE or not DEVICE_PLANE.match(plane)

    planes = []
    for plane in xspace.read(Path(path).read_bytes(), want_line):
        on_device = bool(DEVICE_PLANE.match(plane["name"]))
        meta = plane["metadata"]
        # a device operation: its short name and its path, worked out once
        # per metadata and not per event
        short = {mid: (op_key(m["name"]), m["stats"].get(SCOPE_STAT) or "")
                 for mid, m in meta.items()} if on_device else {}
        lines = []
        for line in plane["lines"]:
            events = []
            for mid, start, dur in line["events"]:
                if on_device:
                    name, scope = short.get(mid, ("", ""))
                    events.append([name, start, dur, scope])
                else:
                    events.append([meta[mid]["name"] if mid in meta else "",
                                   start, dur])
            lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def digest(trace: Mapping[str, Any], head: int = 5) -> List[Any]:
    """Planes, lines, event counts and each line's first events: what to
    look at by hand before trusting a pattern."""
    return [[plane["name"], line["name"], len(line["events"]),
             line["events"][:head]]
            for plane in trace["planes"] for line in plane["lines"]]


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def device_ops(trace: Mapping[str, Any]) -> Dict[int, List[Event]]:
    """Device ordinal -> its operation events, sorted by start."""
    out: Dict[int, List[Event]] = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        events = [tuple(e) for line in plane["lines"]
                  if line["name"] == OP_LINE for e in line["events"]]
        if events:
            out[int(m.group(1))] = sorted(events, key=lambda e: (e[1], -e[2]))
    return out


def host_spans(trace: Mapping[str, Any]) -> List[Event]:
    return sorted((tuple(e[:3]) for plane in trace["planes"]
                   if not DEVICE_PLANE.match(plane["name"])
                   for line in plane["lines"] for e in line["events"]
                   if e[0].startswith(SPAN_PREFIX)), key=lambda e: e[1])


def traced_window(trace: Mapping[str, Any]) -> Interval:
    """The harness's ``bench/traced_window`` span; without one, the extent
    of the device operations."""
    for name, start, dur in host_spans(trace):
        if name == WINDOW_SPAN:
            return start, start + dur
    ops = [e for evs in device_ops(trace).values() for e in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def self_times(events: Sequence[Event], window: Interval
               ) -> List[Tuple[str, int, int, int, bool, str]]:
    """(name, start, end, self_ns, is_leaf, scope) of each event clipped to
    the window; ``events`` sorted by (start, -duration). An event that lies
    inside an earlier, still open one is its child."""
    lo, hi = window
    out: List[List[Any]] = []
    stack: List[int] = []
    for name, start, dur, *scope in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e <= s:
            continue
        # an open event is a parent only if it holds this one whole; one
        # that merely overlaps is a sibling (two engines on one line)
        while stack and out[stack[-1]][2] < e:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[2]) - s
            parent[4] = False
        out.append([name, s, e, e - s, True, scope[0] if scope else ""])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def _selector(pattern: str, scope: Optional[str]
              ) -> Callable[[str, str], bool]:
    """Whether an operation's name matches ``pattern`` and, where ``scope``
    is given, its path matches that."""
    rx = re.compile(pattern)
    if scope is None:
        return lambda name, path: bool(rx.search(name))
    sx = re.compile(scope)
    return lambda name, path: bool(rx.search(name) and sx.search(path))


class Reduced:
    """One trace reduced: what every trace-fed per-layer metric reads."""

    def __init__(self, trace: Mapping[str, Any]):
        self.window = traced_window(trace)
        self.spans = [s for s in host_spans(trace) if s[0] != WINDOW_SPAN]
        self.per_device = {
            dev: self_times(evs, self.window)
            for dev, evs in device_ops(trace).items()}
        self.devices = sorted(self.per_device)
        self._ns: Optional[Dict[Tuple[str, str], int]] = None
        self._busy: Dict[int, List[Interval]] = {}
        if not self.devices:
            raise ValueError("the trace holds no device operation")

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, dev: int) -> List[Interval]:
        if dev not in self._busy:      # every share of busy time asks
            self._busy[dev] = union(
                (s, e) for _, s, e, _, leaf, _ in self.per_device[dev]
                if leaf)
        return self._busy[dev]

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        return sum(total(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def ns_by_name_and_scope(self) -> Dict[Tuple[str, str], int]:
        """Self nanoseconds by (operation name, scope path), summed over
        the devices, in the order first met: the one pass over the events
        that every share is then read from."""
        if self._ns is None:
            acc: Dict[Tuple[str, str], int] = {}
            for dev in self.devices:
                for name, _, _, self_ns, _, scope in self.per_device[dev]:
                    key = (name, scope)
                    acc[key] = acc.get(key, 0) + self_ns
            self._ns = acc
        return self._ns

    def seconds_by_name(self) -> Dict[str, float]:
        """Self seconds by operation name, averaged over the devices."""
        acc: Dict[str, float] = {}
        for (name, _), self_ns in self.ns_by_name_and_scope().items():
            acc[name] = acc.get(name, 0.0) + self_ns
        return {k: v / len(self.devices) / 1e9 for k, v in acc.items()}

    def seconds_by_name_and_scope(self) -> List[Tuple[str, str, float]]:
        """(name, scope, self seconds averaged over the devices), largest
        first: what to read before writing a ``scope`` expression."""
        return sorted(((n, p, v / len(self.devices) / 1e9) for (n, p), v
                       in self.ns_by_name_and_scope().items()),
                      key=lambda row: -row[2])

    def seconds_matching(self, pattern: str,
                         scope: Optional[str] = None) -> float:
        """Self seconds of the operations whose name matches ``pattern``
        and, where ``scope`` is given, whose path matches that too (both
        searched, not anchored; an event with no path has the path "")."""
        rx = re.compile(pattern)
        if scope is None:   # summed name by name, as before there were paths
            return sum(v for k, v in self.seconds_by_name().items()
                       if rx.search(k))
        sx = re.compile(scope)
        return sum(v for (n, p), v in self.ns_by_name_and_scope().items()
                   if rx.search(n) and sx.search(p)) \
            / len(self.devices) / 1e9

    def matching_intervals(self, dev: int, pattern: str,
                           scope: Optional[str] = None) -> List[Interval]:
        mine = _selector(pattern, scope)
        return union((s, e) for n, s, e, _, leaf, p in self.per_device[dev]
                     if leaf and mine(n, p))

    def exposed_seconds(self, pattern: str,
                        scope: Optional[str] = None) -> float:
        """Seconds (mean over devices) in which an operation matching
        ``pattern`` (and ``scope``) ran and no other operation did on that
        device."""
        mine = _selector(pattern, scope)
        acc = 0
        for dev in self.devices:
            others = union((s, e) for n, s, e, _, leaf, p
                           in self.per_device[dev]
                           if leaf and not mine(n, p))
            acc += total(subtract(
                self.matching_intervals(dev, pattern, scope), others))
        return acc / len(self.devices) / 1e9

    def idle_gaps_by_span(self) -> Dict[str, float]:
        """Idle seconds of the first device inside the window, split over
        the host spans that were open meanwhile (``outside_any_span`` for
        the rest)."""
        dev = self.devices[0]
        gaps = subtract([self.window], self.busy(dev))
        acc: Dict[str, float] = {}
        covered: List[Interval] = []
        for name, start, dur in self.spans:
            span = [(start, start + dur)]
            inside = total(span) - total(subtract(span, gaps))
            if inside:
                acc[name] = acc.get(name, 0.0) + inside / 1e9
            covered.append(span[0])
        rest = total(subtract(gaps, union(covered)))
        if rest:
            acc["outside_any_span"] = rest / 1e9
        return acc

    def breakdown(self, top: int = 10) -> Dict[str, List[List[Any]]]:
        def ranked(d: Mapping[str, float]):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]
        return {"device_ops": ranked(self.seconds_by_name()),
                "idle_gaps": ranked(self.idle_gaps_by_span())}
