"""The reader of the program's late-step record (``benchmark/reducers/
program_late.py``) on hand-made ring rows with known answers, and the four
metrics it feeds held to the manifest's rules: 0.0 where the window's steps
are on record and none was late, sums of the events' attributes, the
``unknown`` ones alone, the window's two edges, and nothing at all where
the program has no recorder."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import manifest as M
from benchmark.harness import RunContext
from benchmark.reducers import program_late
from dalle_tpu.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parent.parent.parent
METRICS = checks.LATE_RUN
MAN = M.Manifest()


def ctx_for(n_intervals=4, traced_steps=3):
    return RunContext(values={"n_intervals": n_intervals},
                      traced_steps=traced_steps)


def late(tracer, n, **attrs):
    # the recorder says whether ``where`` began with the step's hook or after
    attrs.setdefault("hook_or_after", int(attrs.get("where") in (
        "loop/hook", "collab/step", "self")))
    tracer.add("train", "loop/late_step", f"step:{n}", 100.0 * n + 2, 0.0,
               **attrs)


@pytest.fixture()
def ring():
    """Ten steps: three of set-up, four of the window (4-7), three traced."""
    tracer = obs_trace.configure(peer="late-test")
    for n in range(1, 11):
        tracer.add("train", "loop/step", f"step:{n}", 100.0 * n, 1.0)
    yield tracer
    obs_trace._default = None


def read_all():
    return {m["name"]: program_late.read(ctx_for(), **m["params"])
            for m in MAN.cell("flagship-train-solo").per_layer
            if m["name"] in METRICS}


def test_a_window_with_no_late_step_reads_zero_not_nothing(ring):
    late(ring, 2, excess_s=9.0, where="loop/hook", cause="host")   # set-up
    late(ring, 9, excess_s=9.0, where="loop/loss_wait", cause="unknown")
    assert read_all() == dict.fromkeys(METRICS, 0.0)


def test_the_windows_events_are_counted_and_summed(ring):
    late(ring, 5, excess_s=1.5, pulse_missed_s=1.4, where="loop/loss_wait",
         cause="process_stopped")
    late(ring, 6, excess_s=0.25, pulse_missed_s=0.0, where="collab/step",
         cause="unknown")
    assert read_all() == {
        "late_steps": 2.0, "late_excess_s": 1.75,
        "late_pulse_missed_s": 1.4, "late_unnamed_s": 0.25}


def test_an_event_without_the_attribute_adds_nothing(ring):
    # a record made where no pulse ran carries no pulse_missed_s
    late(ring, 5, excess_s=0.5, where="loop/loss_wait", cause="unknown")
    got = read_all()
    assert got["late_pulse_missed_s"] == 0.0
    assert got["late_unnamed_s"] == 0.5 and got["late_steps"] == 1.0


@pytest.mark.parametrize("step, where, inside", [
    # the window closes when its last step's hook opens: what that hook
    # then does (a traced run starts the profiler in it) is outside
    (7, "loop/hook", False), (7, "collab/step", False), (7, "self", False),
    (7, "loop/loss_wait", True), (7, "loop/grad_dispatch", True),
    (7, "between_steps", True), (3, "between_steps", False),
    # and it opens with the hook of the step before its first
    (3, "loop/hook", True), (3, "collab/step", True),
    (3, "loop/loss_wait", False),
    (4, "loop/hook", True), (8, "loop/loss_wait", False)])
def test_the_windows_two_edges(ring, step, where, inside):
    late(ring, step, excess_s=2.0, where=where, cause="host")
    assert read_all()["late_steps"] == float(inside)


def test_left_out_where_the_program_has_no_recorder(ring, monkeypatch):
    late(ring, 5, excess_s=1.5, where="loop/loss_wait", cause="unknown")
    monkeypatch.delitem(sys.modules, "dalle_tpu.obs.late", raising=False)
    monkeypatch.setitem(sys.modules, "dalle_tpu.obs.late", None)
    import dalle_tpu.obs
    monkeypatch.delattr(dalle_tpu.obs, "late", raising=False)
    assert set(read_all().values()) == {None}


def test_left_out_where_there_is_no_ring_or_its_steps_are_gone(ring):
    assert program_late.read(ctx_for(n_intervals=0), what="count") is None
    obs_trace._default = None
    assert program_late.read(ctx_for(), what="count") is None
    tracer = obs_trace.configure(peer="late-test")      # events, no steps
    late(tracer, 5, excess_s=1.0, where="loop/hook", cause="host")
    assert program_late.read(ctx_for(), what="count") is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_metric_keeps_to_the_manifest(metric):
    checks.metric_file_agrees(MAN, metric)
    entry = next(m for m in MAN.data["per_layer"] if m["name"] == metric)
    assert entry["layer"] == "task and loop"
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["better"] == "lower" and "workloads" not in entry
    on_file = json.loads(MAN.metric_file(metric).read_text())
    assert on_file["reducer"] == "program_late"
    for cell in MAN.cells:            # every cell, as loop_self_pct
        assert metric in {m["name"] for m in MAN.cell(cell).per_layer}


def test_the_four_metrics_are_one_run_in_order_wherever_it_stands():
    """``benchmark_checks.late_metrics_are_a_run``, which ``every_check``
    applies to a rehearsal's throw-away root too: the four are adjacent and
    in PR 35's order. Where the run stands is not held, so a later PR's
    entries go after it (PR 43; until then this pinned the list's tail and
    any PR that brought a per-layer metric turned it red)."""
    checks.late_metrics_are_a_run(MAN)


def test_a_traced_rehearsal_reports_the_four_and_its_log_agrees(tmp_path):
    """The tiny cell through ``harness.run_cell`` on the CPU with the
    recorder on, as it always is: the four metrics are on the traced
    run's line (0 where the window was quiet, never missing), and every
    late step they count is a line of the run's ``warnings.log``, where an
    untraced run leaves its record."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="4",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "benchmark_rehearse.py"),
         "1", "1", str(tmp_path)], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    got = json.loads(last.split(":", 1)[1])["metrics"]
    for name in METRICS:
        assert name in got, name
    count, excess = got["late_steps"]["value"], got["late_excess_s"]["value"]
    assert count == int(count) >= 0 and excess >= 0.0
    assert (count == 0) == (excess == 0.0)
    assert 0.0 <= got["late_unnamed_s"]["value"] <= excess
    assert got["late_pulse_missed_s"]["value"] >= 0.0
    logged = (tmp_path / "run" / "warnings.log").read_text()
    named = set(re.findall(r"\bstep:\d+(?= took | \+)", logged))
    unnamed = sum(int(n) for n in re.findall(r"; and (\d+) more", logged))
    assert len(named) + unnamed >= count, logged
    assert "Traceback" not in done.stderr
