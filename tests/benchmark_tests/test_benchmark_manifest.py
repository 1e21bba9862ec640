"""BENCHMARK.json and the files it names: every cell resolves, names and
units keep to the contract's characters, the metric files agree with the
manifest, the configuration files hold the presets as they are run, and a
cell added as files plus one entry is found."""
import dataclasses
import json
import shutil

import pytest

from benchmark import manifest as M
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


def test_a_cell_added_as_files_and_one_entry_is_found(tmp_path):
    shutil.copytree(MAN.dir, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = json.loads((MAN.root / "BENCHMARK.json").read_text())
    new_traffic = dict(MAN.cell(CELLS[0]).traffic, grad_accum_steps=64)
    (tmp_path / "benchmark/traffic/solo-256x64.json").write_text(
        json.dumps(new_traffic))
    data["workloads"].append({"name": "flagship-train-solo-a64",
                              "config": "flagship",
                              "traffic": "solo-256x64", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    cell = M.Manifest(tmp_path).cell("flagship-train-solo-a64")
    assert cell.traffic["grad_accum_steps"] == 64
    assert cell.config["preset"] == "flagship"
    with pytest.raises(KeyError):
        M.Manifest(tmp_path).cell("no-such-cell")
