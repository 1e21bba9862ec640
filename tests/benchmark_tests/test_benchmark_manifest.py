"""BENCHMARK.json and the files it names: every cell resolves, names and
units keep to the contract's characters, the metric files agree with the
manifest, the configuration files hold the presets as they are run, and a
cell, or a configuration of another architecture, added as files and
entries is found."""
import dataclasses
import json
import shutil

import pytest

from benchmark import manifest as M
from benchmark.harness import RunContext
from dalle_tpu.cli.run_trainer import MODEL_PRESETS

MAN = M.Manifest()
CELLS = sorted(MAN.cells)


def test_manifest_shape():
    d = MAN.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= d["run_seconds"] <= 51 and isinstance(d["run_seconds"], int)
    assert "setup_s" in {m["name"] for m in d["end_to_end"]}
    four = [w for w in d["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(d["workloads"]) // 4)
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(d)) < 64 * 1024
    assert d["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert not any(w.startswith("/") or ".." in w for w in d["command"])
    used = {w["config"] for w in d["workloads"]}
    assert used == set(MAN.configs)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_its_files(cell_name):
    cell = MAN.cell(cell_name)
    assert cell.chips in (1, 4)
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["per_device_batch"] >= 1
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "train_tokens_per_s"}
    assert cell.per_layer
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(M.reducer(m["reducer"]))


def test_names_units_and_whys_keep_to_the_contract():
    d = MAN.data
    for entry in d["configs"] + d["workloads"] + d["end_to_end"] \
            + d["per_layer"]:
        assert M.NAME.match(entry["name"]), entry["name"]
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert M.NAME.match(w["config"]) and M.NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in d["configs"]:
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in d["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in M.SOURCES
        assert M.UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in MAN.data["per_layer"]])
def test_metric_file_agrees_with_the_manifest(metric):
    entry = next(m for m in MAN.data["per_layer"] if m["name"] == metric)
    on_file = json.loads(MAN.metric_file(metric).read_text())
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert on_file[key] == entry[key], key


@pytest.mark.parametrize("config", sorted(MAN.configs))
def test_configuration_file_holds_the_preset_as_run(config):
    entry = MAN.configs[config]
    on_file = json.loads((MAN.root / entry["file"]).read_text())
    ran = dataclasses.asdict(MODEL_PRESETS[on_file["preset"]]())
    ran = {k: list(v) if isinstance(v, tuple) else v for k, v in ran.items()}
    assert on_file["model"] == ran
    assert on_file["reduced"] == entry["reduced"] == []
    assert on_file["source"] == entry["source"]
    # a size no public source gives is listed as assumed
    assert set(on_file["assumed"]) == (
        {"dim", "heads", "vocab_image"} if config == "xl" else set())


def _copy_of_the_benchmark(tmp_path):
    shutil.copytree(MAN.dir, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((MAN.root / "BENCHMARK.json").read_text())


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
    # a metric that names its cells comes to a new cell as an entry and a
    # file of the cell's own that name the same reducer: no list of an
    # entry that is there is edited, and no code is added
    own = dict(json.loads(MAN.metric_file("attn_roofline").read_text()),
               name="attn_roofline.a64")
    (tmp_path / "benchmark/layer_metrics/attn_roofline.a64.json").write_text(
        json.dumps(own))
    data["per_layer"].append(
        _per_layer_entry(own, ["flagship-train-solo-a64"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    cell = M.Manifest(tmp_path).cell("flagship-train-solo-a64")
    assert cell.traffic["grad_accum_steps"] == 64
    assert cell.config["preset"] == "flagship"
    read = {m["name"]: m for m in cell.per_layer}
    assert "attn_roofline" not in read
    assert read["attn_roofline.a64"]["reducer"] == "kernel_roofline"
    assert read["attn_roofline.a64"]["params"]["least"] \
        == "attention_min_seconds_per_sample"
    with pytest.raises(KeyError):
        M.Manifest(tmp_path).cell("no-such-cell")


# another architecture's yardstick, as a later PR would add it: counts of
# its own, and the least seconds of a kernel family the dalle block lacks
OTHER_YARDSTICK = '''"""A test's stand-in for another architecture."""


def loss_and_grads(params, text, image, model, checkpoint_blocks=False):
    raise NotImplementedError


def tokens_per_sample(model):
    return model["seq_len"]


def train_flops_per_sample(model):
    return 6.0 * model["weights"] * model["seq_len"]


def router_min_seconds_per_sample(model, peaks):
    flops = 2.0 * model["seq_len"] * model["hidden"] * model["experts"]
    return {"seconds": 3.0 * flops / peaks["bf16_flops_per_s"]}
'''


def test_a_configuration_added_as_files_and_entries_is_found(tmp_path):
    """What a ``model_config`` PR brings: a configuration file, its
    yardstick file, a roofline metric as one ``layer_metrics`` file naming
    the common reducer and the yardstick's function, and the entries that
    name them. No file the benchmark had is edited, and the new cell reads
    its kernel's share with no reducer code."""
    data = _copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    d = tmp_path / "benchmark"
    (d / "yardsticks/other.py").write_text(OTHER_YARDSTICK)
    model = {"seq_len": 2048, "hidden": 2048, "experts": 64, "weights": 1e9}
    (d / "configs/other.json").write_text(json.dumps(
        {"name": "other", "yardstick": "other", "model": model}))
    roofline = dict(json.loads(MAN.metric_file("attn_roofline").read_text()),
                    name="router_roofline",
                    params={"pattern": r"^router\[mosaic\]$",
                            "least": "router_min_seconds_per_sample"})
    (d / "layer_metrics/router_roofline.json").write_text(
        json.dumps(roofline))
    data["configs"].append({"name": "other", "source": "test", "reduced": [],
                            "file": "benchmark/configs/other.json",
                            "why": "test"})
    data["workloads"].append({"name": "other-train-solo", "config": "other",
                              "traffic": MAN.cell(CELLS[0]).traffic_name,
                              "chips": 1, "why": "test"})
    data["per_layer"].append(
        _per_layer_entry(roofline, ["other-train-solo"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    man = M.Manifest(tmp_path)
    cell = man.cell("other-train-solo")
    assert cell.yardstick.__file__ == str(d / "yardsticks/other.py")
    assert cell.yardstick.tokens_per_sample(cell.config["model"]) == 2048
    read = {m["name"]: m for m in cell.per_layer}
    assert "router_roofline" in read and "attn_roofline" not in read
    assert "mfu_pct" in read          # a metric of every cell comes along
    # the cells that were there read what they read, by the same yardstick
    old = man.cell("flagship-train-solo")
    assert old.yardstick.__file__ == str(d / "yardsticks/dalle.py")
    assert "router_roofline" not in {m["name"] for m in old.per_layer}

    class Trace:
        @staticmethod
        def seconds_matching(pattern):
            return 0.5 if pattern == roofline["params"]["pattern"] else 0.0

    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = RunContext(model=model, yardstick=cell.yardstick, chips=1,
                     peaks=peaks, trace=Trace(), traced_steps=3,
                     samples_per_step=8,
                     values={"train_tokens_per_s": 5000.0})
    m = read["router_roofline"]
    least = 3.0 * 2.0 * 2048 * 2048 * 64 / 1e12
    assert M.reducer(m["reducer"])(ctx, **m["params"]) == pytest.approx(
        100 * least * 3 * 8 / 0.5)
    assert M.reducer(read["mfu_pct"]["reducer"])(ctx) == pytest.approx(
        100 * 6.0 * 1e9 * 5000.0 / 1e12)
    assert all(p.read_bytes() == raw for p, raw in before.items())
