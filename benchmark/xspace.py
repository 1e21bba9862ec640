"""A reader of the serialized ``XSpace`` the JAX profiler writes
(``*.xplane.pb``), for the few messages the trace reduction needs.

``jax.profiler.ProfileData`` hands out planes, lines and events with their
own stats, but not the stats of an event's *metadata*, and that is where
the profiler keeps the path under which the program issued a device
operation (``tf_op``). So this reads the protobuf wire format itself, with
nothing but Python. The schema is ``tsl/profiler/protobuf/xplane.proto``;
field numbers used::

    XSpace          planes=1
    XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
                    (both maps: entry key=1 value=2)
    XLine           name=2 timestamp_ns=3 events=4
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3
    XEventMetadata  id=1 name=2 stats=5
    XStatMetadata   id=1 name=2
    XStat           metadata_id=1 str_value=5 ref_value=7
                    (a ref_value is the id of a stat metadata whose name
                    is the string)

Times come out as ``ProfileData`` gives them: an event starts at the
line's ``timestamp_ns`` plus its ``offset_ps`` in whole nanoseconds and
lasts its ``duration_ps`` in whole nanoseconds, and its name is its
metadata's ``name``. ``tests/benchmark_tests/test_benchmark_xspace.py``
holds the two readers to the same events on the same bytes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _signed(value: int) -> int:
    """An ``int64`` field as the wire's unsigned varint carries it."""
    return value - (1 << 64) if value >= 1 << 63 else value


def fields(buf: bytes, pos: int, end: int
           ) -> Iterator[Tuple[int, int, int, int]]:
    """(field number, wire type, value or start, end) of each field of the
    message in ``buf[pos:end]``: a varint's value, or the span of a
    length-delimited or fixed field's bytes."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, pos = _varint(buf, pos)
            yield number, wire, value, pos
        elif wire in (BYTES, FIXED64, FIXED32):
            if wire == BYTES:
                size, pos = _varint(buf, pos)
            else:
                size = 8 if wire == FIXED64 else 4
            if pos + size > end:
                raise ValueError(f"a field of {size} bytes at byte {pos} "
                                 f"overruns its message: a cut file?")
            yield number, wire, pos, pos + size
            pos += size
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an "
                             f"XSpace this reader knows")


def _text(buf: bytes, start: int, end: int) -> str:
    return buf[start:end].decode("utf-8", "replace")


def _map_entry(buf: bytes, start: int, end: int) -> Tuple[int, int, int]:
    """Key and the span of the value message of one map entry."""
    key, span = 0, (end, end)
    for number, wire, a, b in fields(buf, start, end):
        if number == 1 and wire == VARINT:
            key = _signed(a)
        elif number == 2 and wire == BYTES:
            span = (a, b)
    return key, span[0], span[1]


def _named(buf: bytes, start: int, end: int
           ) -> Tuple[str, List[Tuple[int, int]]]:
    """``name`` of an XStatMetadata or XEventMetadata, and the spans of an
    event metadata's stats."""
    name, stats = "", []
    for number, wire, a, b in fields(buf, start, end):
        if number == 2 and wire == BYTES:
            name = _text(buf, a, b)
        elif number == 5 and wire == BYTES:
            stats.append((a, b))
    return name, stats


def _stat(buf: bytes, start: int, end: int, stat_names: Dict[int, str]
          ) -> Tuple[str, Any]:
    key, value = 0, None
    for number, wire, a, b in fields(buf, start, end):
        if number == 1 and wire == VARINT:
            key = _signed(a)
        elif number == 5 and wire == BYTES:
            value = _text(buf, a, b)
        elif number == 7 and wire == VARINT:
            value = stat_names.get(a, "")
        elif number in (3, 4) and wire == VARINT:
            value = a if number == 3 else _signed(a)
    return stat_names.get(key, str(key)), value


def _events(buf: bytes, spans: List[Tuple[int, int]], timestamp_ns: int
            ) -> List[Tuple[int, int, int]]:
    """(metadata id, start_ns, duration_ns) of a line's events. The one
    loop that runs a million times: fields read in place."""
    out = []
    for pos, end in spans:
        meta = offset = duration = 0
        while pos < end:
            key = buf[pos]
            pos += 1
            if key >= 0x80:                  # a field number past 15
                key, pos = _varint(buf, pos - 1)
            wire = key & 7
            if wire == VARINT:
                value = shift = 0
                while True:
                    byte = buf[pos]
                    pos += 1
                    value |= (byte & 0x7F) << shift
                    if byte < 0x80:
                        break
                    shift += 7
                if key == 0x08:
                    meta = value
                elif key == 0x10:
                    offset = value
                elif key == 0x18:
                    duration = value
            elif wire == BYTES:
                size, pos = _varint(buf, pos)
                pos += size
            elif wire in (FIXED64, FIXED32):
                pos += 8 if wire == FIXED64 else 4
            else:
                raise ValueError(f"wire type {wire} in an XEvent at byte "
                                 f"{pos - 1}")
        out.append((_signed(meta),
                    timestamp_ns + _signed(offset) // 1000,
                    _signed(duration) // 1000))
    return out


def _line(buf: bytes, start: int, end: int, plane: str,
          want_line: Optional[Callable[[str, str], bool]]
          ) -> Optional[Dict[str, Any]]:
    name, timestamp_ns, spans = "", 0, []
    for number, wire, a, b in fields(buf, start, end):
        if number == 2 and wire == BYTES:
            name = _text(buf, a, b)
        elif number == 3 and wire == VARINT:
            timestamp_ns = _signed(a)
        elif number == 4 and wire == BYTES:
            spans.append((a, b))
    if want_line is not None and not want_line(plane, name):
        return None
    return {"name": name, "events": _events(buf, spans, timestamp_ns)}


def _plane(buf: bytes, start: int, end: int,
           want_line: Optional[Callable[[str, str], bool]]
           ) -> Dict[str, Any]:
    name, lines, event_spans, stat_names = "", [], {}, {}
    for number, wire, a, b in fields(buf, start, end):
        if wire != BYTES:
            continue
        if number == 2:
            name = _text(buf, a, b)
        elif number == 3:
            lines.append((a, b))
        elif number == 4:
            key, s, e = _map_entry(buf, a, b)
            event_spans[key] = (s, e)
        elif number == 5:
            key, s, e = _map_entry(buf, a, b)
            stat_names[key] = _named(buf, s, e)[0]
    metadata = {}
    for key, (s, e) in event_spans.items():
        meta_name, stats = _named(buf, s, e)
        metadata[key] = {"name": meta_name, "stats": dict(
            _stat(buf, a, b, stat_names) for a, b in stats)}
    read_lines = (_line(buf, s, e, name, want_line) for s, e in lines)
    return {"name": name, "metadata": metadata,
            "lines": [line for line in read_lines if line is not None]}


def read(raw: bytes,
         want_line: Optional[Callable[[str, str], bool]] = None
         ) -> List[Dict[str, Any]]:
    """The planes of a serialized ``XSpace``::

        [{"name": plane, "metadata": {id: {"name": ..., "stats": {stat: value}}},
          "lines": [{"name": line, "events": [(metadata id, start_ns, duration_ns)]}]}]

    ``want_line(plane name, line name)`` false leaves a line out before
    its events are read (a device plane has lines of a quarter of a
    million events that the reduction never looks at)."""
    return [_plane(raw, a, b, want_line) for number, wire, a, b
            in fields(raw, 0, len(raw)) if number == 1 and wire == BYTES]
