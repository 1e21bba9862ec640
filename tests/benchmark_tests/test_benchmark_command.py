"""The command itself: without a TPU it prints no result and exits
non-zero; the CPU rehearsals drive the whole of ``run_cell`` at a tiny size
through the test-only hook (interpreted kernels, ``require_backend=None``)
in processes of their own, because the device count is fixed per process."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.manifest import ROOT


def _run(args, env_extra, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, "-m", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_no_tpu_no_result(tmp_path):
    done = _run(["benchmark.run", "--workload", "flagship-train-solo",
                 "--seed", "2147483747", "--seconds", "1", "--trace", "0",
                 "--out-dir", str(tmp_path)], {})
    assert done.returncode != 0
    assert "no result" in done.stderr
    for line in done.stdout.splitlines():
        assert '"metrics"' not in line and '"correct"' not in line


def test_unknown_workload_is_an_error(tmp_path):
    done = _run(["benchmark.run", "--workload", "nope", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], {})
    assert done.returncode != 0 and '"metrics"' not in done.stdout


REHEARSE = Path(__file__).parent / "benchmark_rehearse.py"


def _rehearse(tmp_path, chips, trace, seconds):
    flags = {"SECS": str(seconds), "PYTHONPATH": str(ROOT)}
    if chips > 1:
        flags["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={chips}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(flags)
    done = subprocess.run(
        [sys.executable, str(REHEARSE), str(chips), str(trace),
         str(tmp_path)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    *earlier, last = done.stdout.strip().splitlines()
    assert last.startswith("REHEARSAL")
    return json.loads(last.split(":", 1)[1]), tmp_path / "run", earlier


def test_rehearsal_solo_end_to_end(tmp_path):
    result, out, earlier = _rehearse(tmp_path, 1, 0, 4)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"   # a rehearsal, no result
    window = json.loads((out / "intervals.json").read_text())["windows"][0]
    assert window["n_intervals"] >= 10 and window["window_compiles"] == 0
    assert len(window["intervals_s"]) == window["n_intervals"]
    # the rate is all the steps of the window over all of its wall
    # (4 samples a step: micro 2 x accum 2)
    steps = window["n_intervals"]
    assert result["attempted"] == steps
    per_sample = window["train_tokens_per_s"] * window["wall_s"] / (4 * steps)
    assert per_sample == pytest.approx(round(per_sample)) and per_sample > 1
    assert result["metrics"]["train_tokens_per_s"]["value"] \
        == window["train_tokens_per_s"]
    assert set(window["host"]) >= {"process_cpu_s", "involuntary_switches"}
    # each number that decided ``correct`` beside its limit, last in the line
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"loss_rel", "grad_rel_l2",
                                       "census_missing"}
    for value, limit in result["compared"].values():
        assert value <= limit
    # the line of the reference check carries the census and the program's
    # engagement records: every sentence of the ``setup/warmup`` row, its
    # counts left out; a DALL-E model has no ``moe_layout`` to give
    line = next(json.loads(line) for line in earlier
                if line.startswith('{"reference_check"'))
    assert line["census"]["missing"] == []
    assert line["census"]["unlisted"] == {} == line["census"]["found"]
    said = line["engagement"]
    assert set(said) >= {"attn_layout", "layer_loop", "grad_reduction"}
    assert "moe_layout" not in said and "steps" not in said
    assert all(isinstance(v, str) for v in said.values())


def test_rehearsal_solo_traced(tmp_path):
    result, out, _ = _rehearse(tmp_path, 1, 1, 4)
    assert result["correct"] is True
    got = result["metrics"]
    for name in ("step_interval_s", "stall_pct", "host_freeze_s",
                 "grad_step_s", "loop_overhead_pct", "window_compiles",
                 "compile_s", "init_s", "reference_check_s",
                 "grad_step_plan_gib", "buffers_peak_gib",
                 "program_reserved_gib"):
        assert name in got, name
    assert "train_tokens_per_s" not in got and "setup_s" not in got
    # no device plane on the CPU: every trace-fed metric is left out
    assert "device_idle_pct" not in got and "attn_roofline" not in got
    written = json.loads((out / "intervals.json").read_text())
    assert set(written["spans"]) == set(
        ["bench/batch_fetch", "bench/grad_step_dispatch",
         "bench/grad_step_wait", "bench/collab_step"])
    assert "freezes" in written["windows"][0]
    assert not (out / "trace").exists()      # only the digest stays


@pytest.mark.slow
def test_rehearsal_dp4_on_four_virtual_devices(tmp_path):
    result, _, _ = _rehearse(tmp_path, 4, 0, 4)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
