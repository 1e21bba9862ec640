"""``benchmark/xspace.py``, the reader of the profiler's file that needs
nothing but Python, against ``jax.profiler.ProfileData`` on the same bytes
(every event's name, start and duration equal), and what only it reads: the
stats of an event's *metadata*, where the profiler keeps the scope path.
On an ``XSpace`` built here from a text proto, on one encoded by hand with
fields the reader has to skip, and on a small file the profiler wrote."""
import struct
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from benchmark import trace as T
from benchmark import xspace

RECORDED = Path(__file__).parent / "fixtures" / "cpu_profile.xplane.pb"

# a device plane as the TPU's profiler writes it: the operation's path is a
# stat of the event's metadata, once as a string and once as a reference to
# a stat metadata's name; offsets are no whole nanoseconds; a line the
# reduction does not read; a host plane
TEXT = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 45083530
    events { metadata_id: 7 offset_ps: 1234567 duration_ps: 7654321 }
    events { metadata_id: 8 offset_ps: 9000999 duration_ps: 1999
             stats { metadata_id: 3 int64_value: -5 } }
    events { metadata_id: 9 offset_ps: 9100000 duration_ps: 500000 }
    events { metadata_id: 404 offset_ps: 9900000 duration_ps: 1000 }
  }
  lines { id: 3 name: "Steps" timestamp_ns: 45083530
    events { metadata_id: 7 offset_ps: 0 duration_ps: 99000000 }
  }
  event_metadata { key: 7 value { id: 7
    name: "%fusion.12 = bf16[4]{0} fusion(%p), kind=kLoop"
    display_name: "fusion.12"
    stats { metadata_id: 1 str_value: "jit(f)/jit(main)/cycle/block_0/ff/mul" }
    stats { metadata_id: 3 int64_value: 42 } } }
  event_metadata { key: 8 value { id: 8
    name: "%attn.3 = bf16[4]{0} custom-call(%q), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 9 value { id: 9 name: "%copy.1 = f32[2]{0} copy(%x)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(f)/jit(main)/cycle/block_0/attn" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 5 name: "python3" timestamp_ns: 45000000
    events { metadata_id: 1 offset_ps: 999 duration_ps: 40000000999 }
  }
  lines { id: 6 name: "wall clock" timestamp_ns: 1790545939050391123
    events { metadata_id: 2 offset_ps: 1999 duration_ps: 5000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/traced_window" } }
  event_metadata { key: 2 value { id: 2 name: "past 2**53: a double drops digits" } }
}
'''


def both(raw):
    """Every event as (plane, line, name, start, duration) by each reader.
    ``ProfileData`` hands the times out as doubles."""
    mine = [(p["name"], line["name"],
             p["metadata"].get(mid, {"name": ""})["name"], float(s), float(d))
            for p in xspace.read(raw) for line in p["lines"]
            for mid, s, d in line["events"]]
    theirs = [(p.name, line.name, e.name, e.start_ns, e.duration_ns)
              for p in ProfileData.from_serialized_xspace(raw).planes
              for line in p.lines for e in line.events]
    return mine, theirs


@pytest.fixture(scope="module")
def built():
    return ProfileData.text_proto_to_serialized_xspace(TEXT)


def test_wire_reader_agrees_with_profile_data(built):
    mine, theirs = both(built)
    assert mine == theirs and len(mine) == 7
    # whole nanoseconds, cut and not rounded, as ProfileData has them
    assert mine[0][3:] == (45083530 + 1234, 7654)
    assert mine[1][3:] == (45083530 + 9000, 1)


def test_metadata_stats_are_read_where_profile_data_has_none(built):
    device = xspace.read(built)[0]
    meta = device["metadata"]
    assert meta[7]["stats"] == {
        "tf_op": "jit(f)/jit(main)/cycle/block_0/ff/mul", "flops": 42}
    assert meta[8]["stats"] == {"tf_op": "jit(f)/jit(main)/cycle/block_0/attn"}
    assert meta[9]["stats"] == {}
    # a line that is not wanted is left out before its events are read
    kept = xspace.read(built, lambda plane, line: line != "Steps")
    assert [line["name"] for line in kept[0]["lines"]] == ["XLA Ops"]
    assert [line["name"] for line in device["lines"]] == ["XLA Ops", "Steps"]
    # ProfileData shows an event's own stats only
    event = list(ProfileData.from_serialized_xspace(built).planes)[0]
    own = [dict(e.stats) for line in event.lines for e in line.events]
    assert not any("tf_op" in s for s in own)


def test_load_xplane_keeps_the_scope_path(built, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(built)
    trace = T.load_xplane(path)
    device, host = trace["planes"]
    (ops,) = device["lines"]               # only the XLA Ops line is kept
    assert ops["name"] == "XLA Ops"
    assert ops["events"] == [
        ["fusion", 45084764, 7654, "jit(f)/jit(main)/cycle/block_0/ff/mul"],
        ["attn[mosaic]", 45092530, 1, "jit(f)/jit(main)/cycle/block_0/attn"],
        ["copy", 45092630, 500, ""],
        ["", 45093430, 1, ""]]             # an id with no metadata: no name
    assert [line["events"] for line in host["lines"]] == [
        [["bench/traced_window", 45000000, 40000000]],
        # whole nanoseconds as the file has them, past what a double holds
        [["past 2**53: a double drops digits", 1790545939050391124, 5]]]
    r = T.Reduced(trace)
    assert r.seconds_matching(".", scope="/ff/") == pytest.approx(7654e-9)
    assert r.seconds_matching(r"\[mosaic\]$", scope="/attn$") \
        == pytest.approx(1e-9)
    # no path is the path "": the copy's 500 ns and the nameless event's 1
    assert r.seconds_matching("", scope="^$") == pytest.approx(501e-9)


def test_recorded_file_reads_the_same(tmp_path):
    raw = RECORDED.read_bytes()
    mine, theirs = both(raw)
    assert mine == theirs and len(mine) > 100
    assert {p["name"] for p in xspace.read(raw)} >= {"/host:CPU"}
    # and through load_xplane: host planes keep every line, three elements
    trace = T.load_xplane(RECORDED)
    events = [e for p in trace["planes"] for line in p["lines"]
              for e in line["events"]]
    assert len(events) == len(mine) and {len(e) for e in events} == {3}
    assert [e[0] for e in T.host_spans(trace)] == ["bench/traced_window"]


def varint(n):
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One field on the wire: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_by_hand_with_fields_to_skip():
    """The encoding written out byte by byte, with what a later schema may
    add: a field number past 15, a fixed64 and a fixed32 field, a negative
    ``int64``. The reader skips what it does not know."""
    later = varint(20 << 3 | 1) + struct.pack("<d", 1.5) \
        + varint(21 << 3 | 5) + struct.pack("<f", 2.5) + field(300, 7)
    stat = field(1, 1) + field(5, b"jit(g)/head/dot_general")
    event_meta = field(1, 7) + field(2, b"%dot.1 = f32[2]{0} dot(%a, %b)") \
        + field(5, stat) + later
    event = field(1, 7) + field(2, 2500) + field(3, 1500) + later
    line = field(2, b"XLA Ops") + field(3, 100) + field(4, event) \
        + field(4, field(1, 7) + field(2, -1000) + field(3, 3000)) + later
    plane = field(2, b"/device:TPU:3") + field(3, line) \
        + field(4, field(1, 7) + field(2, event_meta)) \
        + field(5, field(1, 1) + field(2, field(1, 1) + field(2, b"tf_op"))) \
        + later
    raw = field(1, plane) + field(4, b"hostname") + later
    (read,) = xspace.read(raw)
    assert read["name"] == "/device:TPU:3"
    assert read["metadata"] == {7: {
        "name": "%dot.1 = f32[2]{0} dot(%a, %b)",
        "stats": {"tf_op": "jit(g)/head/dot_general"}}}
    assert read["lines"] == [{"name": "XLA Ops",
                              "events": [(7, 102, 1), (7, 99, 3)]}]
    mine, theirs = both(raw)
    assert mine == theirs
    for cut in (raw[:-1], raw[:40], field(1, b"\x0a\x7f")):
        with pytest.raises((ValueError, IndexError)):
            xspace.read(cut)
