"""BENCHMARK.json and the files it names: every cell resolves, names and
units keep to the contract's characters, the metric files agree with the
manifest, the configuration files hold the presets as they are run, and a
cell, or a configuration of another architecture, added as files and
entries is found."""
import dataclasses
import json
import shutil
import types
from pathlib import Path

import benchmark_checks as checks
import pytest

from benchmark import harness
from benchmark import manifest as M
from benchmark.harness import RunContext
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

MAN = M.Manifest()
CELLS = sorted(MAN.cells)
# the benchmark's five cells at PR 43, and the metrics more than one of
# them reads through one entry: a cell a later PR adds joins these lists
# (or brings a copy with parameters of its own) and is not judged here
SPARSE_CELLS = {"smallthinker21b-train-solo", "trinitymini-train-solo"}
CELLS_AT_PR_43 = SPARSE_CELLS | {"flagship-train-solo", "xl-train-solo",
                                 "flagship-train-dp4"}
SHARED = dict({"attn_roofline": CELLS_AT_PR_43}, **dict.fromkeys((
    "moe_experts_roofline", "moe_router_share_pct", "moe_dispatch_share_pct",
    "moe_experts_share_pct", "moe_load_max_over_mean",
    "moe_assignments_here_pct", "moe_dense_calls", "moe_dropped",
    "moe_sum_spills"), SPARSE_CELLS))


def test_manifest_shape():
    checks.manifest_shape(MAN)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_its_files(cell_name):
    checks.cell_resolves_its_files(MAN, cell_name)


def test_names_units_and_whys_keep_to_the_contract():
    checks.names_units_and_whys(MAN)


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in MAN.data["per_layer"]])
def test_metric_file_agrees_with_the_manifest(metric):
    checks.metric_file_agrees(MAN, metric)


@pytest.mark.parametrize("metric", sorted(SHARED))
def test_a_metric_several_cells_read_is_one_entry_that_lists_them(metric):
    """No copy under ``<metric>.<cell>`` (eleven went at PR 43): of the
    five cells exactly those that run the mechanism are listed, every
    listed cell is there and reads the one file through its own yardstick,
    and a roofline's name ends in ``_roofline`` and so is held to ``%``."""
    entry = next(m for m in MAN.data["per_layer"] if m["name"] == metric)
    listed = entry["workloads"]
    assert len(listed) == len(set(listed)) and set(listed) <= set(MAN.cells)
    assert set(listed) & CELLS_AT_PR_43 == SHARED[metric]
    assert not [m["name"] for m in MAN.data["per_layer"]
                if m["name"].startswith(metric + ".")
                and m["name"][len(metric) + 1:] in CELLS_AT_PR_43]
    on_file = json.loads(MAN.metric_file(metric).read_text())
    if on_file["reducer"] == "kernel_roofline":
        assert metric.endswith("_roofline") and entry["unit"] == "%"
    for name in listed:
        cell = MAN.cell(name)
        read = next(m for m in cell.per_layer if m["name"] == metric)
        assert read["params"] == on_file["params"]
        if "least" in read["params"]:
            assert callable(getattr(cell.yardstick, read["params"]["least"]))


@pytest.mark.parametrize("config", sorted(MAN.configs))
def test_configuration_file_holds_the_preset_as_run(config):
    """The rule of ``benchmark/manifest.py``'s docstring; ``flagship`` and
    ``xl`` are pinned besides to exactly what they are."""
    checks.configuration_file(MAN, config, MODEL_PRESETS)


def _copy_of_the_benchmark(tmp_path):
    shutil.copytree(MAN.dir, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((MAN.root / "BENCHMARK.json").read_text())


def _entry(entries, name):
    """The entry of that name in a list of ``BENCHMARK.json``, wherever it
    stands."""
    return next(e for e in entries if e["name"] == name)


def _per_layer_entry(on_file, cells):
    """The ``per_layer`` entry of a metric file, for the named cells."""
    return dict({k: on_file[k] for k in ("name", "unit", "better", "source",
                                         "layer", "moves")}, workloads=cells)


def test_a_cell_added_as_files_and_one_entry_is_found(tmp_path):
    data = _copy_of_the_benchmark(tmp_path)
    new_traffic = dict(MAN.cell(CELLS[0]).traffic, grad_accum_steps=64)
    (tmp_path / "benchmark/traffic/solo-256x64.json").write_text(
        json.dumps(new_traffic))
    data["workloads"].append({"name": "flagship-train-solo-a64",
                              "config": "flagship",
                              "traffic": "solo-256x64", "chips": 1,
                              "why": "test"})
    # a metric that names its cells comes to a new cell by the cell's name
    # appended to the entry's list; a copy under <metric>.<cell>, with a
    # file of its own, only where the cell's parameters differ (here the
    # kernels of one scope alone), and the file says which
    _entry(data["per_layer"], "attn_roofline")["workloads"].append(
        "flagship-train-solo-a64")
    shared = json.loads(MAN.metric_file("attn_roofline").read_text())
    own = dict(shared, name="attn_roofline.a64",
               params=dict(shared["params"], scope="(^|/)axial_row(/|$)"),
               note="differs from attn_roofline by params.scope")
    (tmp_path / "benchmark/layer_metrics/attn_roofline.a64.json").write_text(
        json.dumps(own))
    data["per_layer"].append(
        _per_layer_entry(own, ["flagship-train-solo-a64"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    man = M.Manifest(tmp_path)
    checks.manifest_shape(man)
    checks.names_units_and_whys(man)
    checks.late_metrics_are_a_run(man)
    checks.metric_file_agrees(man, "attn_roofline.a64")
    cell = man.cell("flagship-train-solo-a64")
    assert cell.traffic["grad_accum_steps"] == 64
    assert cell.config["preset"] == "flagship"
    read = {m["name"]: m for m in cell.per_layer}
    assert read["attn_roofline"]["params"] == shared["params"]
    assert read["attn_roofline.a64"]["reducer"] == "kernel_roofline"
    assert read["attn_roofline.a64"]["params"]["least"] \
        == "attention_min_seconds_per_sample"
    for name in CELLS:                 # the cells that were there: unmoved
        assert [m["name"] for m in man.cell(name).per_layer] \
            == [m["name"] for m in MAN.cell(name).per_layer]
    with pytest.raises(KeyError):
        M.Manifest(tmp_path).cell("no-such-cell")


# another architecture's yardstick, as a later PR would add it: counts of
# its own, and the least seconds of a kernel family the dalle block lacks
OTHER_YARDSTICK = '''"""A test's stand-in for another architecture."""


def loss_and_grads(params, text, image, model, checkpoint_blocks=False):
    raise NotImplementedError


def tokens_per_sample(model):
    return model["text_seq_len"] + model["image_grid"] ** 2


def train_flops_per_sample(model):
    active = model["layers"] * model["experts_routed"] * 3 * model[
        "hidden"] * model["expert_width"]
    return 6.0 * active * tokens_per_sample(model)


def router_min_seconds_per_sample(model, peaks):
    flops = 2.0 * tokens_per_sample(model) * model["hidden"] * model[
        "experts_published"] * model["layers"]
    return {"seconds": 3.0 * flops / peaks["bf16_flops_per_s"]}


def attention_min_seconds_per_sample(model, peaks):
    t = tokens_per_sample(model)
    flops = 4.0 * model["hidden"] * model["layers"] * t * (t + 1) / 2
    return {"seconds": 3.0 * flops / peaks["bf16_flops_per_s"]}


def experts_min_seconds_per_sample(model, peaks):
    return {"seconds": train_flops_per_sample(model) * model[
        "experts_held"] / model["experts_published"]
        / peaks["bf16_flops_per_s"]}
'''


@dataclasses.dataclass(frozen=True)
class StandInConfig:
    """A preset of another class than the program's ``ModelConfig``, cut to
    one chip's share: the five fields the harness reads of every model, and
    a few of its own."""
    vocab_text: int = 37984                 # a quarter of 151 936 rows
    vocab_image: int = 8192
    text_seq_len: int = 1024
    image_grid: int = 32
    param_dtype: str = "float32"
    hidden: int = 2560
    expert_width: int = 768
    layers: int = 4                         # one period of 52
    layer_pattern: tuple = ("global", "window", "window", "window")
    experts_published: int = 64             # the router's width, never cut
    experts_held: int = 16
    experts_routed: int = 6
    norm_eps: float = 1e-6


STANDIN = "standin"
STANDIN_FILE = {
    "name": STANDIN, "preset": STANDIN, "yardstick": "other",
    "source": "test", "reduced": ["layers", "experts_held", "vocab_text"],
    "assumed": ["text_seq_len", "image_grid"],
    "published": {"layers": 52, "experts_held": 64, "vocab_text": 151936},
    "layer_shared_by": 4,
    "deployment": "each layer shared by the four chips of one host; this "
                  "chip holds 16 of 64 experts and a quarter of the "
                  "vocabulary, and one period of the layer pattern",
    "mosaic_kernels": [],
    "tolerance": {"loss_rel": 1e-4, "grad_rel_l2": 1e-3, "reason": "test"}}


def _standin_root(tmp_path, presets, mutate=None):
    """A throw-away root with the stand-in added as files and entries.
    ``mutate(data, on_file)`` spoils the manifest's data or the stand-in's
    file before they are written."""
    data = _copy_of_the_benchmark(tmp_path)
    d = tmp_path / "benchmark"
    (d / "yardsticks/other.py").write_text(OTHER_YARDSTICK)
    on_file = dict(json.loads(json.dumps(STANDIN_FILE)),
                   model=checks.as_run(presets[STANDIN]()))
    roofline = dict(json.loads(MAN.metric_file("attn_roofline").read_text()),
                    name="router_roofline",
                    params={"pattern": r"^router\[mosaic\]$",
                            "least": "router_min_seconds_per_sample"})
    (d / "layer_metrics/router_roofline.json").write_text(
        json.dumps(roofline))
    data["configs"].append({"name": STANDIN, "source": "test",
                            "reduced": list(on_file["reduced"]),
                            "file": f"benchmark/configs/{STANDIN}.json",
                            "why": "test"})
    data["workloads"].append({"name": "standin-train-solo",
                              "config": STANDIN,
                              "traffic": MAN.cell(CELLS[0]).traffic_name,
                              "chips": 1, "why": "test"})
    data["per_layer"].append(
        _per_layer_entry(roofline, ["standin-train-solo"]))
    # what the next sparse configuration does with the metrics the sparse
    # cells share: its cell's name on each list, and no copy
    for metric in SHARED:
        _entry(data["per_layer"], metric)["workloads"].append(
            "standin-train-solo")
    if mutate is not None:
        mutate(data, on_file)
    (d / f"configs/{STANDIN}.json").write_text(json.dumps(on_file))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    return M.Manifest(tmp_path)


@pytest.fixture()
def presets(monkeypatch):
    """``MODEL_PRESETS`` with the stand-in's preset registered, as the
    ``model_config`` PR's program would have it."""
    monkeypatch.setitem(MODEL_PRESETS, STANDIN, StandInConfig)
    return MODEL_PRESETS


def test_a_configuration_added_as_files_and_entries_is_found(tmp_path,
                                                             presets):
    """What a ``model_config`` PR brings: a configuration file (here one
    **cut to a chip's share**, of a dataclass that is not the program's
    ``ModelConfig``), its yardstick file, a roofline metric as one
    ``layer_metrics`` file naming the common reducer and the yardstick's
    function, the entries that name them **at the ends of the real lists**,
    and its cell's name on the list of each metric the sparse cells share.
    It then meets **every** check the suite applies per configuration,
    cell, metric and yardstick — the same functions the parametrised tests
    call, the run of ``late_*`` among them — and ``check_model``. No file
    the benchmark had is edited, and the new cell reads its kernels'
    shares, the shared ones through its own yardstick, with no reducer
    code."""
    _copy_of_the_benchmark(tmp_path / "before")
    before = {p.relative_to(tmp_path / "before"): p.read_bytes()
              for p in (tmp_path / "before/benchmark").rglob("*")
              if p.is_file()}
    man = _standin_root(tmp_path / "root", presets)
    d = man.dir

    checks.every_check(man, presets)
    cell = man.cell("standin-train-solo")
    model = cell.config["model"]
    assert model["layer_pattern"] == ["global", "window", "window", "window"]
    stub = types.SimpleNamespace(model_cfg=StandInConfig())
    harness.check_model(stub, cell)
    with pytest.raises(M.BenchFailure, match="standin: experts_held is 8"):
        harness.check_model(types.SimpleNamespace(
            model_cfg=StandInConfig(experts_held=8)), cell)

    assert cell.yardstick.__file__ == str(d / "yardsticks/other.py")
    assert cell.yardstick.tokens_per_sample(model) == 2048
    read = {m["name"]: m for m in cell.per_layer}
    assert set(read) >= {"router_roofline", *SHARED}
    assert "mfu_pct" in read          # a metric of every cell comes along
    # the cells that were there read what they read, by the same yardstick
    for name in CELLS:
        old = man.cell(name)
        assert old.yardstick.__file__ == str(
            d / "yardsticks" / Path(MAN.cell(name).yardstick.__file__).name)
        assert [m["name"] for m in old.per_layer] \
            == [m["name"] for m in MAN.cell(name).per_layer]

    timed = {(read[name]["params"]["pattern"],
              read[name]["params"].get("scope"))
             for name in ("router_roofline", "attn_roofline",
                          "moe_experts_roofline")}

    class Trace:
        @staticmethod
        def seconds_matching(pattern, scope=None):
            return 0.5 if (pattern, scope) in timed else 0.0

    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = RunContext(model=model, yardstick=cell.yardstick, chips=1,
                     peaks=peaks, trace=Trace(), traced_steps=3,
                     samples_per_step=8,
                     values={"train_tokens_per_s": 5000.0})
    m = read["router_roofline"]
    least = 3.0 * 2.0 * 2048 * 2560 * 64 * 4 / 1e12
    assert M.reducer(m["reducer"])(ctx, **m["params"]) == pytest.approx(
        100 * least * 3 * 8 / 0.5)
    # the shared rooflines: one file, this cell's own yardstick's functions
    for name, least in (
            ("attn_roofline", 3.0 * 4.0 * 2560 * 4 * 2048 * 2049 / 2 / 1e12),
            ("moe_experts_roofline",
             6.0 * 4 * 6 * 3 * 2560 * 768 * 2048 * 16 / 64 / 1e12)):
        m = read[name]
        assert M.reducer(m["reducer"])(ctx, **m["params"]) == pytest.approx(
            100 * least * 3 * 8 / 0.5), name
    flops_per_token = 6.0 * 4 * 6 * 3 * 2560 * 768
    assert M.reducer(read["mfu_pct"]["reducer"])(ctx) == pytest.approx(
        100 * flops_per_token * 5000.0 / 1e12)
    after = {p.relative_to(tmp_path / "root"): p.read_bytes()
             for p in d.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: raw for p, raw in after.items() if p in before} == before
    assert sorted(str(p) for p in set(after) - set(before)) == [
        "benchmark/configs/standin.json",
        "benchmark/layer_metrics/router_roofline.json",
        "benchmark/yardsticks/other.py"]


def _late_at(data, name):
    return [m["name"] for m in data["per_layer"]].index(name)


def _entry_inside_the_run(data, on_file):
    """The fault PR 43 repaired, the other way round: the stand-in's
    roofline entry not at the end of the list but between two of the four."""
    data["per_layer"].insert(_late_at(data, "late_excess_s"),
                             data["per_layer"].pop())


def _run_out_of_order(data, on_file):
    a, b = _late_at(data, "late_excess_s"), _late_at(data,
                                                     "late_pulse_missed_s")
    data["per_layer"][a], data["per_layer"][b] = (data["per_layer"][b],
                                                  data["per_layer"][a])


def _one_of_the_four_gone(data, on_file):
    del data["per_layer"][_late_at(data, "late_unnamed_s")]


def _one_of_the_four_lists_cells(data, on_file):
    _entry(data["per_layer"], "late_steps")["workloads"] = [
        "standin-train-solo"]


@pytest.mark.parametrize("mutate, message", [
    (_entry_inside_the_run,
     r"per_layer: 'router_roofline' stands where 'late_excess_s' belongs"),
    (_run_out_of_order,
     r"per_layer: 'late_pulse_missed_s' stands where 'late_excess_s' "
     r"belongs"),
    (_one_of_the_four_gone, r"per_layer names late_unnamed_s 0 times"),
    (_one_of_the_four_lists_cells, r"per_layer: late_steps lists workloads"),
])
def test_a_list_that_breaks_the_run_of_late_metrics_is_refused(
        tmp_path, presets, mutate, message):
    """``every_check`` holds a rehearsal's root to the run too, and the
    message names the metric that stands in the wrong place."""
    man = _standin_root(tmp_path, presets, mutate)
    with pytest.raises(AssertionError, match=message):
        checks.late_metrics_are_a_run(man)
    with pytest.raises(AssertionError, match=message):
        checks.every_check(man, presets)


def _no_such_key(data, on_file):
    on_file["reduced"].append("no_such_key")
    on_file["published"]["no_such_key"] = 1
    _entry(data["configs"], STANDIN)["reduced"].append("no_such_key")


def _no_published(data, on_file):
    del on_file["published"]


def _entry_disagrees(data, on_file):
    _entry(data["configs"], STANDIN)["reduced"] = ["layers"]


def _role_that_does_not_compile(data, on_file):
    on_file["mosaic_kernels"] = ["_fwd_kernel", "_causal_(?!fwd_"]


def _cut_flagship(root):
    """``flagship`` given a cut that the general rule would admit: only
    its pin refuses it. (A throw-away copy: no PR may edit the real one.)"""
    def mutate(data, on_file):
        path = root / "benchmark/configs/flagship.json"
        flagship = json.loads(path.read_text())
        flagship.update(reduced=["depth"], published={"depth": 128},
                        layer_shared_by=1)
        path.write_text(json.dumps(flagship))
        _entry(data["configs"], "flagship")["reduced"] = ["depth"]
    return mutate


@pytest.mark.parametrize("case, config, message", [
    ("no_such_key", STANDIN,
     r"configuration standin: reduced names 'no_such_key'.*no key of model"),
    ("no_published", STANDIN,
     r"configuration standin: reduced is \[.*\] and the file has no "
     r"published"),
    ("entry_disagrees", STANDIN,
     r"configuration standin: reduced is \['layers', 'experts_held', "
     r"'vocab_text'\] in the file and \['layers'\] in BENCHMARK.json"),
    ("cut_flagship", "flagship",
     r"configuration flagship: reduced is pinned to \[\], not \['depth'\]"),
    ("role_that_does_not_compile", STANDIN,
     r"configuration standin: mosaic_kernels entry '_causal_\(\?!fwd_' is no "
     r"regular expression"),
])
def test_a_configuration_that_breaks_the_rule_is_refused(
        tmp_path, presets, case, config, message):
    mutate = {"no_such_key": _no_such_key, "no_published": _no_published,
              "entry_disagrees": _entry_disagrees,
              "cut_flagship": _cut_flagship(tmp_path),
              "role_that_does_not_compile": _role_that_does_not_compile}[case]
    man = _standin_root(tmp_path, presets, mutate)
    with pytest.raises(AssertionError, match=message):
        checks.configuration_file(man, config, presets)
    # the other configurations of that root still pass
    for other in sorted(set(man.configs) - {config}):
        checks.configuration_file(man, other, presets)


@pytest.mark.parametrize("config, key, value", [
    ("flagship", "assumed", ["dim"]), ("xl", "assumed", []),
    ("xl", "assumed", ["dim", "heads"])])
def test_the_two_pinned_configurations_keep_their_assumed(
        tmp_path, presets, config, key, value):
    def mutate(data, on_file):
        path = tmp_path / f"benchmark/configs/{config}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **{key: value})))
    man = _standin_root(tmp_path, presets, mutate)
    with pytest.raises(AssertionError,
                       match=f"configuration {config}: {key} is pinned"):
        checks.configuration_file(man, config, presets)


def test_the_rehearsal_root_takes_a_preset_of_another_class(tmp_path,
                                                            presets):
    """``benchmark_rehearse.tiny_root`` builds its one cell from any preset,
    its overrides and its ``run_trainer`` flags; left out, today's tiny
    DALL-E."""
    import benchmark_rehearse
    cell = benchmark_rehearse.tiny_root(
        tmp_path / "other", yardstick="dalle", preset=STANDIN,
        overrides={"experts_held": 8, "layers": 2},
        trainer_args=["--experts-held", 8, "--layers", 2])
    assert cell.config["preset"] == STANDIN
    assert cell.config["model"] == checks.as_run(
        StandInConfig(experts_held=8, layers=2))
    assert cell.traffic["trainer_args"] == ["--experts-held", 8,
                                            "--layers", 2]
    harness.check_model(types.SimpleNamespace(
        model_cfg=StandInConfig(experts_held=8, layers=2)), cell)
    argv = harness.trainer_argv(cell, seed=5)
    assert argv[:2] == ["--preset", STANDIN] and argv[-2:] == ["--layers",
                                                               "2"]
    plain = benchmark_rehearse.tiny_root(tmp_path / "plain")
    over, args = benchmark_rehearse.tiny_dalle("float32")
    assert plain.config["preset"] == "tiny"
    assert plain.traffic["trainer_args"] == [
        list(a) if isinstance(a, tuple) else a for a in args]
    assert plain.config["model"]["depth"] == over["depth"] == 10
    assert plain.config["model"]["attn_types"] == list(over["attn_types"])
