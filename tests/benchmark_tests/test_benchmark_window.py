"""The step clock and the heartbeat beside it, on a clock the test holds."""
import threading
import time

import pytest

from benchmark import harness


class Clock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now


@pytest.fixture()
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", c)
    return c


def _drive(window, clock, step_s, n, first=1):
    for i in range(first, first + n):
        clock.now += step_s
        window.on_step(i, 1.0)


def test_window_opens_after_setup_and_closes_on_a_boundary(clock):
    w = harness.Window(seconds=10.0, repeat=1, setup_steps=2, trace_dir=None)
    _drive(w, clock, 3.0, 2)            # step 1 passes, step 2 arms
    assert w.armed and not w.stamps
    _drive(w, clock, 3.0, 1, first=3)   # step 3 opens the window
    assert len(w.stamps) == 1
    with pytest.raises(harness._WindowOver):
        _drive(w, clock, 3.0, 10, first=4)
    (closed,) = w.windows
    # four steps of 3 s: the first boundary at or past 10 s
    assert len(closed["stamps"]) == 5 and len(closed["losses"]) == 4
    assert closed["stamps"][-1] - closed["stamps"][0] == pytest.approx(12.0)
    assert "involuntary_switches" in closed["host"]


def test_repeat_measures_windows_back_to_back(clock):
    w = harness.Window(seconds=4.0, repeat=3, setup_steps=1, trace_dir=None)
    with pytest.raises(harness._WindowOver):
        _drive(w, clock, 2.0, 50)
    assert [len(x["losses"]) for x in w.windows] == [2, 2, 2]


def test_host_counters_only_grow():
    a = harness.host_counters()
    sum(i * i for i in range(200_000))
    b = harness.host_counters()
    assert set(a) == set(b) >= {"process_cpu_s", "involuntary_switches",
                                "major_faults"}
    assert all(b[k] >= a[k] for k in a) and b["process_cpu_s"] > 0


def test_watch_names_a_late_step_and_not_a_usual_one():
    w = harness.Window(seconds=60.0, repeat=1, setup_steps=0, trace_dir=None)
    spans = harness.Spans()
    t0 = time.perf_counter()
    w.stamps = [t0 - 0.2, t0 - 0.15, t0 - 0.1, t0 - 0.05, t0]
    watch = harness.Watch(w, spans)
    watch.BEAT = 0.005
    spans.open = "bench/grad_step_wait"
    watch.start()
    try:
        time.sleep(0.03)                 # under 1.25 x 0.05 s: nothing yet
        assert watch.late_steps == []
        time.sleep(0.15)                 # the step is now three times late
    finally:
        watch.stop()
    (late,) = watch.late_steps           # once a step, not once a beat
    assert late["span"] == "bench/grad_step_wait"
    assert late["step_open_s"] > 1.25 * 0.05
    assert any("test_benchmark_window" in line for line in late["stack"])
    assert watch.freezes == [] and not watch.is_alive()
    inside = watch.inside(t0 - 0.2, time.perf_counter())
    assert len(inside["late_steps"]) == 1 and inside["freezes"] == []
    assert threading.active_count() >= 1
