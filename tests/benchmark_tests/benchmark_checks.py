"""The checks the suite applies to every configuration, cell, per-layer
metric and yardstick of a manifest, each as a function of the manifest
(test-only helper, like ``benchmark_rehearse.py``).

The parametrised tests call them on the repo's own ``BENCHMARK.json``; the
rehearsal of ``test_benchmark_manifest.py`` calls the same functions on a
throw-away root that holds a stand-in configuration of another
architecture, cut to one chip's share. So what a ``model_config`` PR will
meet when it adds its entry is met here first, by a test, on the same
lines. The rule a configuration's file is held to is written out in the
docstring of ``benchmark/manifest.py``.
"""
import ast
import dataclasses
import inspect
import json
import re
from pathlib import Path

from benchmark import manifest as M
from benchmark.harness import peaks_for

# the two configurations the benchmark had before the rule: held to exactly
# what they are (reduced, assumed), whatever the general rule would admit
PINNED = {"flagship": ([], set()),
          "xl": ([], {"dim", "heads", "vocab_image"})}

# PR 35's four late-step metrics, which the ledger and PERF.md read side
# by side: held together as a run, in this order, wherever in the list the
# run stands. What comes before or after it is not held.
LATE_RUN = ("late_steps", "late_excess_s", "late_pulse_missed_s",
            "late_unnamed_s")


def as_run(model_cfg):
    """A preset's dataclass as a configuration file holds it: every field,
    tuples as lists (what JSON makes of them)."""
    return json.loads(json.dumps(dataclasses.asdict(model_cfg)))


def manifest_shape(man):
    d = man.data
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
    assert used == set(man.configs)


def cell_resolves_its_files(man, cell_name):
    cell = man.cell(cell_name)
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


def names_units_and_whys(man):
    d = man.data
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
        assert len(c["reduced"]) <= 16
        assert all(M.NAME.match(key) for key in c["reduced"]), c["reduced"]
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


def late_metrics_are_a_run(man):
    """The four ``late_*`` metrics are in ``per_layer`` once each, adjacent,
    in ``LATE_RUN``'s order, and every cell reports them (no ``workloads``
    list). A later PR's entries go at the end of the list, after the run
    or after whatever already follows it; one put inside the run is named."""
    names = [m["name"] for m in man.data["per_layer"]]
    for name in LATE_RUN:
        assert names.count(name) == 1, (
            f"per_layer names {name} {names.count(name)} times, not once")
    start = names.index(LATE_RUN[0])
    stands = (names + [None] * len(LATE_RUN))[start:start + len(LATE_RUN)]
    for want, got in zip(LATE_RUN, stands):
        assert got == want, (
            f"per_layer: {got!r} stands where {want!r} belongs: the four "
            f"late_* metrics are one run, in the order {list(LATE_RUN)}, "
            f"and a new entry goes at the end of the list")
    for m in man.data["per_layer"][start:start + len(LATE_RUN)]:
        assert "workloads" not in m, (
            f"per_layer: {m['name']} lists workloads; every cell reports "
            f"the late_* metrics")


def metric_file_agrees(man, metric):
    entry = next(m for m in man.data["per_layer"] if m["name"] == metric)
    on_file = json.loads(man.metric_file(metric).read_text())
    for key in ("name", "layer", "unit", "better", "source", "moves"):
        assert on_file[key] == entry[key], key
    for key in ("pattern", "scope"):        # expressions that compile
        if key in on_file.get("params", {}):
            re.compile(on_file["params"][key])


def configuration_file(man, config, presets):
    """The rule of ``benchmark/manifest.py``'s docstring, for one
    configuration. Every message names the configuration and the key."""
    entry = man.configs[config]
    on_file = json.loads((man.root / entry["file"]).read_text())
    who = f"configuration {config}"
    ran = as_run(presets[on_file["preset"]]())
    assert on_file["model"] == ran, (
        f"{who}: model is not preset {on_file['preset']!r} as it is run: "
        + str({k: (on_file["model"].get(k), ran.get(k))
               for k in set(ran) | set(on_file["model"])
               if on_file["model"].get(k) != ran.get(k)}))
    assert on_file["reduced"] == entry["reduced"], (
        f"{who}: reduced is {on_file['reduced']} in the file and "
        f"{entry['reduced']} in BENCHMARK.json")
    assert on_file["source"] == entry["source"], f"{who}: source differs"
    for role in on_file["mosaic_kernels"]:
        try:
            re.compile(role)
        except re.error as e:
            raise AssertionError(f"{who}: mosaic_kernels entry {role!r} is "
                                 f"no regular expression: {e}") from e
    reduced, assumed = on_file["reduced"], on_file["assumed"]
    for listed, keys in (("reduced", reduced), ("assumed", assumed)):
        for key in keys:
            assert key in on_file["model"], (
                f"{who}: {listed} names {key!r}, which is no key of model")
    if reduced:
        assert "published" in on_file, (
            f"{who}: reduced is {reduced} and the file has no published")
        published = on_file["published"]
        assert sorted(published) == sorted(reduced), (
            f"{who}: published gives {sorted(published)}, reduced lists "
            f"{sorted(reduced)}")
        for key in reduced:
            assert published[key] != on_file["model"][key], (
                f"{who}: {key} is listed as reduced and holds the "
                f"published value {published[key]!r}")
        shared = on_file.get("layer_shared_by")
        assert isinstance(shared, int) and not isinstance(shared, bool) \
            and shared >= 1, (
                f"{who}: layer_shared_by is {shared!r}, not the number of "
                f"chips that share each layer")
        deployment = on_file.get("deployment")
        assert isinstance(deployment, str) and deployment.strip(), (
            f"{who}: a cut needs the deployment it stands for")
    else:
        for key in ("published", "layer_shared_by"):
            assert key not in on_file, (
                f"{who}: reduced is empty and the file has {key}")
    if config in PINNED:
        pinned_reduced, pinned_assumed = PINNED[config]
        assert reduced == pinned_reduced, (
            f"{who}: reduced is pinned to {pinned_reduced}, not {reduced}")
        # a size no public source gives is listed as assumed
        assert set(assumed) == pinned_assumed, (
            f"{who}: assumed is pinned to {sorted(pinned_assumed)}, not "
            f"{sorted(assumed)}")


def configuration_resolves_a_yardstick_that_counts(man, config):
    on_file = json.loads((man.root / man.configs[config]["file"]).read_text())
    cells = [man.cell(w["name"]) for w in man.data["workloads"]
             if w["config"] == config]
    assert cells
    peaks = peaks_for("TPU v5 lite")
    for cell in cells:
        y, model = cell.yardstick, cell.config["model"]
        assert Path(y.__file__) == man.dir / "yardsticks" / (
            on_file.get("yardstick", "dalle") + ".py")
        assert callable(y.loss_and_grads)
        tokens = y.tokens_per_sample(model)
        assert isinstance(tokens, int) and tokens > 0
        assert 0 < y.train_flops_per_sample(model) < float("inf")
        # every roofline share this cell reads names a function that is there
        for m in cell.per_layer:
            if m["reducer"] == "kernel_roofline":
                least = getattr(y, m["params"]["least"])(model, peaks)
                assert 0 < least["seconds"] < float("inf")


def yardstick_module_keeps_the_contract(man, name):
    y = man.yardstick(name)

    def names(f):
        return list(inspect.signature(f).parameters)

    assert names(y.loss_and_grads) == ["params", "text", "image", "model",
                                       "checkpoint_blocks"]
    assert inspect.signature(y.loss_and_grads).parameters[
        "checkpoint_blocks"].default is False
    assert names(y.tokens_per_sample) == ["model"]
    assert names(y.train_flops_per_sample) == ["model"]
    used = {json.loads(p.read_text()).get("params", {}).get("least")
            for p in (man.dir / "layer_metrics").glob("*.json")} - {None}
    for least in used & set(dir(y)):
        assert names(getattr(y, least))[:2] == ["model", "peaks"]
    # the reference takes nothing from the program
    tree = ast.parse(Path(y.__file__).read_text())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module or "" for n in ast.walk(tree)
        if isinstance(n, ast.ImportFrom)}
    assert not [m for m in imported
                if m.split(".")[0] in ("dalle_tpu", "flax")], imported
    assert (y.__doc__ or "").strip()


def every_check(man, presets):
    """All of the above over everything the manifest names: what the
    parametrised tests do one case at a time."""
    manifest_shape(man)
    names_units_and_whys(man)
    late_metrics_are_a_run(man)
    for cell_name in sorted(man.cells):
        cell_resolves_its_files(man, cell_name)
    for metric in man.data["per_layer"]:
        metric_file_agrees(man, metric["name"])
    for config in sorted(man.configs):
        configuration_file(man, config, presets)
        configuration_resolves_a_yardstick_that_counts(man, config)
    for path in sorted((man.dir / "yardsticks").glob("*.py")):
        yardstick_module_keeps_the_contract(man, path.stem)
