"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own under ``bench/``; ``BENCHMARK.json`` at the root of
the checkout names them.  Adding a cell, a deployment or a metric adds files
and entries and edits none:

* ``BENCHMARK.json`` ``configs[].file`` — the configuration (JSON);
* ``bench/traffic/<traffic>.json`` — the traffic mix (JSON);
* ``bench/metrics/<metric>.py`` — the reader of one metric: a function
  ``read(run)`` that returns a number, or ``None`` where the run holds
  nothing for it to read;
* ``bench/references/<recipe>.py`` — a plain reference recipe, named by a
  configuration's ``reference.recipe``: ``build(corpus, sample, **params)``
  and ``control(corpus, sample, **params)``, each returning an object with
  ``n_docs``, ``scores_of(queries, ids)`` and ``topk(queries, k)`` (the
  control is the recipe one step coarser).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

from bench.loadgen import Traffic

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's content
    config_entry: dict    # its BENCHMARK.json entry
    traffic: Traffic
    end_to_end: list      # metric entries this cell reports with --trace 0
    per_layer: list       # ... and with --trace 1


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic_path = root / "bench" / "traffic" / f"{w['traffic']}.json"
    traffic = Traffic.from_dict(w["traffic"],
                                json.loads(traffic_path.read_text()))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                config_entry=entry, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def _load(path: Path, kind: str, name: str) -> ModuleType:
    """The module at ``path`` (a name may hold dots, so the file is loaded
    by path, not imported by module name)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return _load(path, "metric", name).read


def reference_recipe(name: Optional[str], root: Path = ROOT) -> ModuleType:
    """The recipe module ``bench/references/<name>.py``.  A configuration
    that names no recipe, or one with no file, is an error: there is no
    default."""
    folder = root / "bench" / "references"
    path = folder / f"{name}.py"
    if not name or not path.is_file():
        have = sorted(p.name for p in folder.glob("*.py"))
        raise FileNotFoundError(
            f"reference recipe {name!r} has no file in {folder}; the "
            f"configuration's reference.recipe names one of {have}")
    return _load(path, "reference", name)


def read_metrics(entries: list, run, root: Path = ROOT
                 ) -> dict[str, dict]:
    """``{name: {"value", "unit"}}`` for each entry whose reader found
    something to read."""
    out: dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = metric_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
