"""The memory account's five per-layer metrics (PR 41; entries of
``BENCHMARK.json`` since PR 43): their files under
``benchmark/layer_metrics/`` held to the manifest's rules, read by
``reducers/program_attr.py`` from hand-made ``loop/step`` rows with known
answers, and all five on the line of a traced rehearsal behind a fake
allocator."""
import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import manifest as M
from benchmark.harness import RunContext
from benchmark.reducers import program_attr
from dalle_tpu.obs import memory as account
from dalle_tpu.obs import trace as obs_trace
from scripts.memory_metrics_run import MEMORY_METRICS, with_entries

ROOT = Path(__file__).resolve().parent.parent.parent
#: metric -> (the ``loop/step`` attribute it reads, how it reduces it)
READS = {"loop_in_use_peak_gib": ("mem_step_max", max),
         "accumulate_transient_gib": ("mem_accumulate_transient",
                                      statistics.median),
         "memory_unowned_gib": ("mem_unowned", statistics.median),
         "loop_reserved_gib": ("mem_reserved", max),
         "state_bytes_per_param": ("mem_state_bytes_per_param",
                                   statistics.median)}


@pytest.fixture(scope="module")
def man():
    return M.Manifest()


def test_the_manifest_with_the_entries_passes_every_check(man):
    checks.manifest_shape(man)
    checks.names_units_and_whys(man)
    for cell in sorted(man.cells):
        checks.cell_resolves_its_files(man, cell)


@pytest.mark.parametrize("metric", MEMORY_METRICS)
def test_the_metric_keeps_to_the_manifest(man, metric):
    checks.metric_file_agrees(man, metric)
    entry = next(m for m in man.data["per_layer"] if m["name"] == metric)
    assert entry["layer"] == "device"
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == "program_span"
    assert entry["better"] == "lower" and "workloads" not in entry
    on_file = json.loads(man.metric_file(metric).read_text())
    assert on_file["reducer"] == "program_attr"
    attr, reduce = READS[metric]
    assert on_file["params"] == {
        "phase": "loop/step", "attr": attr,
        "reduce": {max: "max", statistics.median: "median"}[reduce]}
    assert attr in account.STEP_ATTRIBUTES
    assert (entry["unit"] == "GiB") == metric.endswith("_gib")
    for cell in man.cells:                 # all five cells report it
        assert metric in {m["name"] for m in man.cell(cell).per_layer}


def test_the_five_are_in_the_list_each_once_as_their_files_have_them():
    """Since PR 43 ``BENCHMARK.json`` names the five, each once and as its
    file has it, so ``scripts/memory_metrics_run.with_entries``, which
    appended them to a copy until then, finds nothing to add."""
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in data["per_layer"]]
    for metric in MEMORY_METRICS:
        assert names.count(metric) == 1, metric
        checks.metric_file_agrees(M.Manifest(), metric)
    assert with_entries(data, ROOT / "benchmark" / "layer_metrics") == data


@pytest.fixture()
def ring():
    """Ten steps: three of set-up, four of the window (4-7), three traced;
    each row with the account's attributes, larger every step."""
    tracer = obs_trace.configure(peer="memory-test")
    for n in range(1, 11):
        tracer.add("train", "loop/step", f"step:{n}", 100.0 * n, 1.0,
                   **{attr: n + i / 10 for i, attr in enumerate(
                       account.STEP_ATTRIBUTES)})
    yield tracer
    obs_trace._default = None


@pytest.mark.parametrize("metric", MEMORY_METRICS)
def test_the_windows_steps_are_read_and_no_others(man, ring, metric):
    ctx = RunContext(values={"n_intervals": 4}, traced_steps=3)
    params = json.loads(man.metric_file(metric).read_text())["params"]
    attr, reduce = READS[metric]
    offset = account.STEP_ATTRIBUTES.index(attr) / 10
    assert program_attr.read(ctx, **params) == pytest.approx(
        reduce([n + offset for n in (4, 5, 6, 7)]))


def test_left_out_where_the_program_keeps_no_account(man):
    """The parent of the PR that added the account: rows with no such
    attribute. Nothing to read, no error."""
    tracer = obs_trace.configure(peer="memory-test")
    try:
        for n in range(1, 11):
            tracer.add("train", "loop/step", f"step:{n}", 100.0 * n, 1.0,
                       moe_dropped=0.0)
        ctx = RunContext(values={"n_intervals": 4}, traced_steps=3)
        for metric in MEMORY_METRICS:
            params = json.loads(man.metric_file(metric).read_text())["params"]
            assert program_attr.read(ctx, **params) is None
    finally:
        obs_trace._default = None


def test_a_traced_rehearsal_fills_all_five_from_a_fake_allocator(tmp_path):
    """The tiny cell through ``harness.run_cell`` on the CPU, its account
    behind ``memory_rehearse.fake_allocator``: every metric is on the
    line and reads what the fake device held, and ``engagement`` carries
    the warm-up row's ``memory_layout``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", SECS="4",
               PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "memory_rehearse.py"),
         "1", str(tmp_path)], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL")
    result = json.loads(last.split(":", 1)[1])
    assert result["correct"] is True
    got = {name: result["metrics"][name]["value"] for name in MEMORY_METRICS}
    line = [json.loads(line) for line in done.stdout.splitlines()
            if line.startswith('{"reference_check"')][0]
    said = line["engagement"]["memory_layout"]
    per_param = float(said.split(" = ")[1].split(" B a parameter")[0])
    assert got["state_bytes_per_param"] == pytest.approx(per_param, abs=0.01)
    gib = lambda held: held / 2 ** 30
    assert got["memory_unowned_gib"] == pytest.approx(gib(3 * 2 ** 20),
                                                      abs=2e-6)
    assert got["loop_reserved_gib"] == pytest.approx(gib(5 * 2 ** 20),
                                                     abs=2e-6)
    # the peak is the owners, the code, and the accumulator a second
    # time: 4 of the owners' bytes a parameter
    owners = (got["loop_in_use_peak_gib"] - got["memory_unowned_gib"]
              - got["accumulate_transient_gib"])
    assert got["accumulate_transient_gib"] == pytest.approx(
        owners * 4.0 / per_param, rel=2e-3)
    assert "Traceback" not in done.stderr
