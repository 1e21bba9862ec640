"""The trace reduction: on a hand-made trace with known answers, and on a
small trace recorded on the chip (40 ms of a traced window of the flagship
grad step, cut by PR 23) against a brute-force count on a time grid."""
import json
from pathlib import Path

import pytest

from benchmark import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.json"

HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["while", 100, 800], ["fusion.1", 100, 200],
            ["attn_mosaic", 350, 150], ["all-reduce", 900, 50]]},
        {"name": "Steps", "events": [["1", 0, 1000]]}]},
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench/traced_window", 0, 1000], ["bench/a", 0, 120],
        ["bench/b", 120, 780], ["other", 0, 1000]]}]}]}


def test_hand_made_trace():
    r = T.Reduced(HAND)
    assert r.window == (0, 1000) and r.devices == [0]
    # busy is where a leaf operation runs: the while's own 450 ns are not
    assert r.busy_s == pytest.approx(400e-9)
    by_name = r.seconds_by_name()
    assert by_name["while"] == pytest.approx(450e-9)   # 800 - 200 - 150
    assert by_name["fusion.1"] == pytest.approx(200e-9)
    assert r.seconds_matching("mosaic") == pytest.approx(150e-9)
    assert r.exposed_seconds("^all-reduce") == pytest.approx(50e-9)
    gaps = r.idle_gaps_by_span()
    # idle: 0-100, 300-350, 500-900, 950-1000
    assert gaps["bench/a"] == pytest.approx(100e-9)
    assert gaps["bench/b"] == pytest.approx(450e-9)
    assert gaps["outside_any_span"] == pytest.approx(50e-9)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    top = r.breakdown()
    assert top["device_ops"][0][0] == "while"
    assert top["idle_gaps"][0] == ["bench/b", pytest.approx(450e-9)]


def test_overlap_is_not_exposed():
    trace = {"planes": [{"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["all-reduce.3", 0, 100],
                                       ["fusion", 40, 100]]}]}]}
    r = T.Reduced(trace)
    # fusion starts inside the all-reduce and outlives it: not its child's
    # time to take, and only the first 40 ns are exposed
    assert r.exposed_seconds("^all-reduce") == pytest.approx(40e-9)
    assert r.busy_s == pytest.approx(140e-9)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        T.Reduced({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_interval_helpers():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 9)], []) == [(0, 4), (6, 9)]


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_recorded_trace_against_a_grid(recorded):
    r = T.Reduced(recorded)
    lo, hi = r.window
    ops = T.device_ops(recorded)[r.devices[0]]
    assert len(ops) > 100
    lo, hi = r.window
    # brute force: leaves by comparing every pair, busy time by a sweep
    # over the interval boundaries
    # (an event of no length is no work and makes nothing a parent)
    leaf = [(s, s + d) for n, s, d in ops
            if d and not any(d2 and s <= s2 and s2 + d2 <= s + d
                             and (s2, d2) != (s, d) for _, s2, d2 in ops)]
    points = sorted([(max(s, lo), 1) for s, e in leaf]
                    + [(min(e, hi), -1) for s, e in leaf])
    depth = busy_ns = 0
    for (t, step), (t_next, _) in zip(points, points[1:]):
        depth += step
        if depth > 0:
            busy_ns += t_next - t
    assert r.busy_s == pytest.approx(busy_ns / 1e9, rel=1e-9)
    mosaic = r.seconds_matching("mosaic")
    assert 0 < mosaic < r.busy_s
    gaps = r.idle_gaps_by_span()
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    assert any(k.startswith("bench/") for k in gaps)
