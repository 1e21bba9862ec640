"""The trace reduction: on a hand-made trace with known answers, and on a
small trace recorded on the chip (40 ms of a traced window of the flagship
grad step, cut by PR 23) against a brute-force count on a time grid. Since
PR 27 a device event may carry its scope path: by hand, on 6 ms of the
flagship's trace cut with the paths (a microbatch boundary: the end of one
backward pass, the accumulate, the next embedding), and the events of three
elements reduce to the numbers they always did."""
import json
import re
from pathlib import Path

import pytest

from benchmark import manifest as M
from benchmark import trace as T
from benchmark.harness import RunContext

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.json"
SCOPED = Path(__file__).parent / "fixtures" / "small_trace_scoped.json"

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


# -- the scope path (PR 27) -------------------------------------------------

FF = "jit(f)/while/body/closed_call/cycle/block_0/ff/dot_general:"
ATTN = "jit(f)/while/body/closed_call/cycle/block_0/attn/"
HAND_SCOPED = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["while", 100, 800, ""], ["fusion", 100, 200, FF],
            ["attn[mosaic]", 350, 150, ATTN + "pallas_call:"],
            ["fusion", 520, 80, ATTN + "q/dot_general:"],
            ["all-reduce", 900, 50]]}]},       # three elements: no path
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench/traced_window", 0, 1000]]}]}]}
NOT_MOSAIC = r"^(?!.*\[mosaic\]$)"


def test_scope_by_hand():
    r = T.Reduced(HAND_SCOPED)
    assert r.busy_s == pytest.approx(480e-9)
    # one name, two layers: only the path tells them apart
    assert r.seconds_matching("^fusion$") == pytest.approx(280e-9)
    assert r.seconds_matching("^fusion$", scope="/ff/") \
        == pytest.approx(200e-9)
    assert r.seconds_matching(NOT_MOSAIC, scope="(^|/)attn(/|:|$)") \
        == pytest.approx(80e-9)
    assert r.seconds_matching("", scope="(^|/)attn(/|:|$)") \
        == pytest.approx(230e-9)
    # an event without a path has the path "": the while's own 370 ns
    # (800 - 200 - 150 - 80) and the all-reduce
    assert r.seconds_matching("", scope="^$") == pytest.approx(420e-9)
    assert r.matching_intervals(0, "", scope="/attn/") == [(350, 500),
                                                           (520, 600)]
    assert r.exposed_seconds("^all-reduce", scope="^$") \
        == pytest.approx(50e-9)
    assert r.exposed_seconds("^all-reduce", scope="/ff/") == 0
    assert r.seconds_by_name_and_scope()[0] == (
        "while", "", pytest.approx(370e-9))
    # the reducers pass the parameter through
    share = M.reducer("trace_share")
    ctx = RunContext(trace=r)
    assert share(ctx, pattern=NOT_MOSAIC, scope="/attn/", of="busy") \
        == pytest.approx(100 * 80 / 480)
    assert share(ctx, pattern="^fusion$", of="busy") \
        == pytest.approx(100 * 280 / 480)
    assert share(ctx, pattern="^all-reduce", scope="^$", of="window",
                 exposed=True) == pytest.approx(100 * 50 / 1000)


def _without_paths(trace):
    return {"planes": [{"name": p["name"], "lines": [
        {"name": line["name"], "events": [e[:3] for e in line["events"]]}
        for line in p["lines"]]} for p in trace["planes"]]}


def _all_numbers(r):
    coll = "^(all-reduce|all-gather|reduce-scatter|collective-permute)"
    return json.dumps([r.busy_s, r.window_s, r.seconds_by_name(),
                       r.seconds_matching(r"\[mosaic\]$"),
                       r.exposed_seconds(coll), r.idle_gaps_by_span(),
                       r.breakdown()])


def test_three_elements_reduce_to_the_same_numbers(recorded):
    """What the parent's reduction gave for the PR 23 fixture, digit for
    digit (its events have three elements), and a trace's numbers do not
    depend on whether its events carry paths."""
    r = T.Reduced(recorded)
    assert {len(e) for e in T.device_ops(recorded)[0]} == {3}
    assert (r.busy_s, r.window_s) == (0.019633303, 0.04)
    assert r.seconds_matching("mosaic") == 0.004202379
    assert r.seconds_matching(r"^attn\[mosaic\]$") == 0.002435593
    assert r.breakdown()["device_ops"][:3] == [
        ["while", 0.012398659],
        ["bitcast_dynamic-update-slice_fusion", 0.005331123],
        ["attn[mosaic]", 0.002435593]]
    scoped = json.loads(SCOPED.read_text())
    assert {len(e) for e in T.device_ops(scoped)[0]} == {4}
    assert _all_numbers(T.Reduced(scoped)) \
        == _all_numbers(T.Reduced(_without_paths(scoped)))


# the layers by plain string work, for the expressions of the metric files
# to be held against
SHARES = {"ff_xla_share_pct": {"ff"}, "attn_xla_share_pct": {"attn"},
          "head_ce_share_pct": {"head", "ce"}, "embed_share_pct": {"embed"},
          "grad_accumulate_share_pct": {"grad_accumulate"}}


def _share_of(name, path):
    if name.endswith("[mosaic]"):
        return "mosaic_share_pct"
    parts = [c for c in path.replace(":", "/").split("/") if c]
    for metric, modules in SHARES.items():
        if modules & set(parts):
            return metric
    if any(c.endswith("norm") for c in parts):
        return None                           # LayerNorm left to XLA
    if parts[-2:] == ["transformer", "while"] \
            or parts[-4:-1] == ["transformer", "while", "body"]:
        return "layer_scan_share_pct"
    # control flow's own time is not busy time, and a collective has a
    # metric of its own (``collective_pct``)
    dark = name not in ("while", "call", "conditional") and not re.match(
        "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
        name)
    return "unscoped_share_pct" if dark else None


def test_recorded_paths_split_busy_time_into_disjoint_shares():
    man = M.Manifest()
    scoped = json.loads(SCOPED.read_text())
    r = T.Reduced(scoped)
    ctx = RunContext(trace=r)
    metrics = ["mosaic_share_pct", "layer_scan_share_pct",
               "unscoped_share_pct", *SHARES]
    files = {m: json.loads(man.metric_file(m).read_text()) for m in metrics}
    by_hand = dict.fromkeys(metrics, 0.0)
    for name, path, seconds in r.seconds_by_name_and_scope():
        # each operation falls to at most one share, the one its path says
        taken = [m for m, f in files.items()
                 if re.search(f["params"]["pattern"], name)
                 and re.search(f["params"].get("scope", ""), path)]
        want = _share_of(name, path)
        assert taken == ([want] if want else []), (name, path, taken)
        if want:
            by_hand[want] += seconds
    read = {m: M.reducer(f["reducer"])(ctx, **f["params"])
            for m, f in files.items()}
    for m in metrics:
        assert files[m]["reducer"] == "trace_share" \
            and files[m]["params"]["of"] == "busy"
        assert read[m] == pytest.approx(100 * by_hand[m] / r.busy_s,
                                        rel=1e-9), m
        assert read[m] > 0, m           # the slice holds some of each
    # together: all of busy time but the XLA part of the norms (none here)
    assert sum(read.values()) == pytest.approx(100.0, abs=0.05)
    paths = {p for _, p, _ in r.seconds_by_name_and_scope()}
    assert any("/transformer/while/body/closed_call/cycle/block_0/ff/" in p
               for p in paths)

