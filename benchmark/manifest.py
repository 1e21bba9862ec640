r"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``:

- configuration ``<c>``  -> the entry's ``file`` (``benchmark/configs/<c>.json``)
- traffic mix ``<t>``    -> ``benchmark/traffic/<t>.json``
- per-layer metric ``<m>`` -> ``benchmark/layer_metrics/<m>.json``, which
  names a reducer ``<r>`` -> ``benchmark/reducers/<r>.py`` (``read(ctx, **params)``)
- the configuration file's ``yardstick`` ``<y>`` (absent: ``dalle``) ->
  ``benchmark/yardsticks/<y>.py`` under the manifest's own root: the
  architecture's plain reference and counts (contract: ``yardsticks/dalle.py``)

so a later PR adds a cell, or a configuration of another architecture, by
adding files and entries, and edits nothing.

**How a later PR adds to ``per_layer``** (``benchmark_checks.every_check``
holds the repo's manifest and every rehearsal's throw-away root to it):

- a new metric is one file under ``layer_metrics/`` and one entry **at the
  end of the list**. No test holds the list's tail; the four ``late_*``
  entries are held to being adjacent and in order, wherever that run stands
  (``benchmark_checks.late_metrics_are_a_run``), so nothing goes between them.
- a cell that reads a metric another cell already reads, with the same
  reducer and ``params``, **appends its name to that entry's ``workloads``**
  and brings no file: ``kernel_roofline``'s ``least`` names a function that
  each cell's own yardstick resolves, so one entry serves every architecture
  (``attn_roofline`` lists every cell, the ``moe_*`` ones the sparse cells).
  A metric with no ``workloads`` list is read by every cell already.
- a copy under ``<metric>.<cell>``, with a file of its own, is for a cell
  whose ``params`` differ, and the file's ``note`` says in which. No check
  forbids a copy; eleven that differed by ``name`` alone went at PR 43.

**What a configuration's file holds** (``tests/benchmark_tests/
benchmark_checks.py:configuration_file`` holds every configuration of
``BENCHMARK.json`` to it, and a stand-in of another architecture in a
throw-away root besides):

- ``name``, ``preset``, ``source`` (equal to the entry's), ``model``,
  ``reduced`` (equal to the entry's), ``assumed``, ``mosaic_kernels``,
  ``tolerance``; optionally ``yardstick``, ``deployment``, notes.
- ``model`` is ``dataclasses.asdict(MODEL_PRESETS[preset]())`` of
  ``cli/run_trainer``, tuples as lists: the preset **as it is run**, cut
  included. The dataclass may be of any class; the harness itself reads
  five of its fields to draw the check's batch and judge the parameters
  (``vocab_text``, ``text_seq_len``, ``vocab_image``, ``image_grid``,
  ``param_dtype``), the yardstick whatever it needs.
- every name in ``reduced`` (keys changed from the source: depth, the
  experts or heads held here, a slice of the vocabulary; never a width)
  and in ``assumed`` (sizes no public source gives, e.g. the split of a
  sequence into the trainer's ``text`` / ``image`` fields) is a key of
  ``model``.
- a **cut** configuration (``reduced`` not empty) also holds
  ``published``: the source's value for exactly the keys in ``reduced``,
  each different from the value held; ``layer_shared_by``: the number
  (>= 1) of chips that share each layer in the deployment the cut stands
  for (guide ``model-configs`` section 4: not all the chips the model
  needs); and ``deployment``, that deployment in words. An uncut one
  (``reduced`` empty) has neither ``published`` nor ``layer_shared_by``.
- ``mosaic_kernels`` lists everything the configuration's step must run
  on Mosaic. An entry names a **role**, as a regular expression that
  ``harness.mosaic_census`` ``re.fullmatch``es against the ``kernel_name``s
  of the lowered grad step: a plain name is its own expression
  (``_bwd_kernel`` is not filled by ``_win_bwd_kernel``), and
  ``_causal_(?!fwd_)\w+`` says "attention's backward, however many kernels
  that is". A role no kernel fills is ``missing`` and the run is
  ``correct: false``; a kernel no role names is ``unlisted``, printed with
  its count in the run's ``census`` record and failing nothing: it is
  where the next ``benchmark`` PR sees a list fall behind. So a PR that
  fuses, splits or renames kernels inside a role edits no file, one that
  adds a kernel runs and shows under ``unlisted``, and one whose layer
  leaves Mosaic for the XLA lowering fails. Every entry compiles as an
  expression.
- ``flagship`` and ``xl`` are pinned besides to what they are:
  ``reduced`` ``[]`` in both, ``assumed`` ``[]`` and ``dim``, ``heads``,
  ``vocab_image``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchFailure(RuntimeError):
    """The run cannot give a result (no TPU, a phase of set-up failed, a
    file the manifest names is not there)."""


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, manifest: "Manifest", entry: Dict[str, Any]):
        self.name: str = entry["name"]
        self.chips: int = entry["chips"]
        self.config_name: str = entry["config"]
        self.traffic_name: str = entry["traffic"]
        cfg_entry = manifest.configs[self.config_name]
        self.config = _load(manifest.root / cfg_entry["file"])
        self.yardstick = manifest.yardstick(
            self.config.get("yardstick", "dalle"))
        self.traffic = _load(manifest.traffic_file(self.traffic_name))
        self.end_to_end: List[Dict[str, Any]] = [
            m for m in manifest.data["end_to_end"] if self._has(m)]
        self.per_layer: List[Dict[str, Any]] = [
            dict(m, **_load(manifest.metric_file(m["name"])))
            for m in manifest.data["per_layer"] if self._has(m)]

    def _has(self, metric: Dict[str, Any]) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


class Manifest:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.data = _load(self.root / "BENCHMARK.json")
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.cells = {w["name"]: w for w in self.data["workloads"]}
        self.dir = self.root / self.data["paths"][0]

    def traffic_file(self, name: str) -> Path:
        return self.dir / "traffic" / f"{name}.json"

    def metric_file(self, name: str) -> Path:
        return self.dir / "layer_metrics" / f"{name}.json"

    def yardstick(self, name: str) -> ModuleType:
        """``<benchmark dir>/yardsticks/<name>.py``, loaded by its path: a
        file a later PR (or a test's throw-away root) adds is found with no
        edit to the package. An unknown name is an error, never ``dalle``."""
        path = self.dir / "yardsticks" / f"{name}.py"
        if not (isinstance(name, str) and NAME.match(name)
                and path.is_file()):
            have = sorted(p.stem for p in path.parent.glob("*.py"))
            raise BenchFailure(f"no yardstick {name!r} under {path.parent}; "
                               f"have {have}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_yardstick_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"have {sorted(self.cells)}")
        return Cell(self, self.cells[name])


def reducer(name: str) -> Callable[..., Optional[float]]:
    """``benchmark/reducers/<name>.py``'s ``read``."""
    if not NAME.match(name):
        raise ValueError(f"bad reducer name {name!r}")
    return importlib.import_module(f"benchmark.reducers.{name}").read
