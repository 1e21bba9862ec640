"""The five per-layer metrics of set-up (PR 54) and the reducer that reads
what ``program_counter`` and ``program_span`` cannot
(``benchmark/reducers/program_setup.py``): the files and their entries at
the end of ``per_layer`` meet every check of the suite, on the repo's
manifest and on a rehearsal's throw-away root; on a hand-made ring and
counter each reads a known answer; and on a program that keeps none of it
(the parent with these files laid over: no self seconds, no ``by_site``,
no ``setup/account`` event, no ``trace/site`` span) each reads ``None`` and
raises nothing, so that the metric is left out of the parent's line.

(The account of the real step at real widths is held on the path
``test_benchmark_census.py`` pays for, one lowering a configuration:
``conftest.py`` of this directory.)"""
import json

import benchmark_checks as checks
import pytest
from benchmark_rehearse import tiny_root

from benchmark.harness import RunContext
from benchmark.manifest import Manifest, reducer
from benchmark.reducers import program_counter, program_setup
from dalle_tpu.cli.run_trainer import MODEL_PRESETS
from dalle_tpu.obs import compiles
from dalle_tpu.obs import trace as obs_trace

MAN = Manifest()
#: name -> (layer, source, reducer), in the order of the list's tail
METRICS = {
    "state_init_jit_self_s": ("task and loop", "program_counter",
                              "program_setup"),
    "setup_jit_self_s": ("entry points", "program_counter", "program_setup"),
    "kernel_sites_trace_s": ("model and kernels", "program_span",
                             "program_span"),
    "kernel_sites_again_trace_s": ("model and kernels", "program_span",
                                   "program_span"),
    "kernel_site_again_calls": ("model and kernels", "program_counter",
                                "program_setup")}
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


def read(name, ctx=None):
    """The metric as ``harness.run_cell`` reads it: its file's reducer on
    its file's parameters."""
    on_file = json.loads(MAN.metric_file(name).read_text())
    return reducer(on_file["reducer"])(
        ctx or RunContext(values={}, traced_steps=0),
        **on_file.get("params", {}))


@pytest.mark.parametrize("name", METRICS)
def test_a_metrics_file_and_entry_agree_and_every_cell_reads_it(name):
    layer, source, reads_with = METRICS[name]
    checks.metric_file_agrees(MAN, name)
    entry = next(m for m in MAN.data["per_layer"] if m["name"] == name)
    assert entry == {"name": name, "unit": entry["unit"], "better": "lower",
                     "source": source, "layer": layer, "moves": "setup_s"}
    assert entry["unit"] == ("calls" if name.endswith("_calls") else "s")
    on_file = json.loads(MAN.metric_file(name).read_text())
    assert on_file["reducer"] == reads_with and len(on_file["note"]) > 60
    for cell in MAN.cells:
        assert name in {m["name"] for m in MAN.cell(cell).per_layer}


def test_the_five_entries_are_the_lists_tail_in_the_issues_order():
    names = [m["name"] for m in MAN.data["per_layer"]]
    at = names.index("state_init_jit_self_s")
    assert names[at:at + 5] == list(METRICS)
    # after every entry the benchmark had
    assert at > names.index("sparse_selected_pct")
    # and the twins they stand beside are as they were
    for kept in ("state_init_jit_s", "grad_step_trace_lower_s",
                 "task_state_init_s"):
        assert names.index(kept) < at


def test_the_repos_manifest_passes_every_check():
    checks.every_check(MAN, MODEL_PRESETS)


def test_a_rehearsals_root_passes_the_checks_of_its_lists_and_files(
        tmp_path):
    """``every_check``'s checks of the lists, the cell and the metric
    files, one by one: a rehearsal's one cell is on every metric's list,
    the other architectures' rooflines among them, which its yardstick
    cannot count, so the yardstick's check is the one left out. (The tiny
    preset as it is, so that the file holds a preset as run.)"""
    cell = tiny_root(tmp_path, overrides={}, trainer_args=[])
    assert set(METRICS) <= {m["name"] for m in cell.per_layer}
    man = Manifest(tmp_path)
    checks.manifest_shape(man)
    checks.names_units_and_whys(man)
    checks.late_metrics_are_a_run(man)
    checks.cell_resolves_its_files(man, cell.name)
    for metric in man.data["per_layer"]:
        checks.metric_file_agrees(man, metric["name"])
    checks.configuration_file(man, "tiny", MODEL_PRESETS)


# -- on a hand-made program ---------------------------------------------------

@pytest.fixture
def program():
    """A tracer and a counter as a run leaves them: set-up spans, a traced
    step with nested events, the sites' spans, the account at the first
    step's close, two steady steps."""
    tracer = obs_trace.configure(peer="setup-test")
    counter = compiles.install(tracer)
    clock = iter(range(10 ** 6))
    span = lambda phase, **kw: tracer.span("train", phase, "setup", **kw)
    with span("setup/train_state"):
        # ``init`` is traced inside ``wrapped``: JAX says the inner first
        counter.on_duration(TRACE, 0.0, fun_name="init")
        counter.on_duration(TRACE, 0.0, fun_name="wrapped")
        counter.on_duration(COMPILE, 0.0, fun_name="jit(wrapped)")
    yield tracer, counter
    compiles.install(None)
    obs_trace._default = None


def feed(counter, events):
    """``events``: (kind event, seconds, program), each ending now."""
    for event, seconds, program in events:
        counter.on_duration(event, seconds, fun_name=program)


def test_the_counters_self_form_is_read_by_span(program, monkeypatch):
    """``state_init_jit_self_s``: each second of JAX's machinery once. The
    inclusive twin reads the nested trace twice."""
    tracer, counter = program
    by_span = {"setup/train_state": dict(
        compiles._tally(), trace_n=2, trace_s=13.0, trace_self_s=8.0,
        lower_n=1, lower_s=2.0, lower_self_s=1.5, compile_n=1,
        compile_s=0.75)}
    snap = dict(counter.snapshot(), by_span=by_span)
    monkeypatch.setattr(program_counter, "snapshot", lambda: snap)
    assert read("state_init_jit_self_s") == 8.0 + 1.5 + 0.75
    assert read("state_init_jit_s") == 13.0 + 2.0 + 0.75
    by_span.pop("setup/train_state")
    assert read("state_init_jit_self_s") is None


def test_a_real_counters_self_seconds_stay_under_the_span(program):
    """Events fed as JAX feeds them, inner first, each ending as it is
    reported: 0.3 s inside 0.5 s reads 0.5 s of self and 0.8 inclusive."""
    import time
    tracer, counter = program
    with tracer.span("train", "setup/warmup", "setup"):
        time.sleep(0.3)
        counter.on_duration(TRACE, 0.3, fun_name="inner")
        counter.on_duration(TRACE, 0.0, fun_name="inner")   # cached
        time.sleep(0.2)
        counter.on_duration(TRACE, 0.5, fun_name="outer")
    row = counter.snapshot()["by_span"]["setup/warmup"]
    assert row["trace_s"] == pytest.approx(0.8)
    assert row["trace_self_s"] == pytest.approx(0.5)
    (warm,) = [r for r in tracer.dump() if r["phase"] == "setup/warmup"]
    assert program_setup.each_second_once(row) <= warm["dur_s"]


def test_the_account_and_the_sites_are_read(program, monkeypatch):
    tracer, counter = program
    # the sites' spans: a first trace, the same key again with a site
    # nested in it (the union counts the nested seconds once), another key
    tracer.add("train", "trace/site", "setup", 100.0, 2.0, site="a", key="k",
               nth=1)
    tracer.add("train", "trace/site_again", "setup", 103.5, 0.5,
               parent="trace/site_again", site="b", key="j", nth=2)
    tracer.add("train", "trace/site_again", "setup", 103.0, 1.5, site="a",
               key="k", nth=2)
    tracer.add("train", "trace/site", "-", 110.0, 0.25, site="b", key="j",
               nth=1)
    assert read("kernel_sites_trace_s") == pytest.approx(2.0 + 1.5 + 0.25)
    assert read("kernel_sites_again_trace_s") == pytest.approx(1.5)
    by_site = {"a": {"calls": 21, "keys": 1, "trace_s": 9.0, "again_n": 20,
                     "again_s": 8.0},
               "b": {"calls": 3, "keys": 2, "trace_s": 1.0, "again_n": 1,
                     "again_s": 0.1}}
    snap = dict(counter.snapshot(), by_site=by_site)
    monkeypatch.setattr(program_counter, "snapshot", lambda: snap)
    assert read("kernel_site_again_calls") == 21.0
    # the account: what the total held when the first step closed, not
    # what it holds at the run's end
    assert read("setup_jit_self_s") is None          # no step has closed
    tracer.add("train", "loop/step", "step:1", 120.0, 1.0)
    tracer.event("train", "setup/account", "setup", wall_s=40.0,
                 trace_self_s=20.5, lower_self_s=6.25, compile_s=3.0)
    assert read("setup_jit_self_s") == 20.5 + 6.25 + 3.0


# -- on the parent, with these files laid over --------------------------------

def parent_tally():
    return {"trace_n": 3, "trace_s": 4.0, "lower_n": 1, "lower_s": 1.0,
            "compile_n": 1, "compile_s": 0.5, "cache_hits": 1,
            "cache_misses": 0}


@pytest.fixture
def parent(monkeypatch):
    """What PR 53's program keeps: a counter whose tallies hold the
    inclusive keys alone and no ``by_site``, a ring with the set-up spans
    and the steps and nothing of the sites or the account."""
    tracer = obs_trace.configure(peer="parent")
    tracer.add("train", "setup/train_state", "setup", 10.0, 8.0)
    tracer.add("train", "setup/warmup", "setup", 40.0, 3.0)
    for n in (1, 2, 3):
        tracer.add("train", "loop/step", f"step:{n}", 50.0 + n, 0.5)
    snap = {"total": parent_tally(),
            "by_program": {"grad_step": parent_tally()},
            "by_span": {"setup/train_state": parent_tally()},
            "after_first_step": []}
    monkeypatch.setattr(program_counter, "snapshot", lambda: snap)
    yield snap
    obs_trace._default = None


@pytest.mark.parametrize("name", METRICS)
def test_on_the_parent_a_reader_finds_nothing_and_raises_nothing(name,
                                                                 parent):
    assert read(name) is None
    # the accepted twins read the parent as they did
    assert read("state_init_jit_s") == 5.5
    assert read("task_state_init_s") == 8.0


@pytest.mark.parametrize("name", METRICS)
def test_with_no_counter_and_no_ring_a_reader_finds_nothing(name,
                                                            monkeypatch):
    monkeypatch.setattr(program_counter, "snapshot", lambda: None)
    monkeypatch.setattr(obs_trace, "_default", None)
    assert read(name) is None


def test_an_unknown_reading_is_an_error_not_a_silent_none(parent):
    with pytest.raises(ValueError, match="unknown reading"):
        program_setup.read(RunContext(values={}), what="by_sight")
