"""A cell's files, found by the names in ``BENCHMARK.json``.

* ``BENCHMARK.json`` (checkout root): the cell's configuration, traffic
  and chips, and which metrics it reports;
* the configuration's ``file`` (``bench/configs/<config>.json``): sizes,
  and the ``scorer`` that serves them;
* ``bench/scorers/<scorer>.py``: how the server is built for that scorer
  and the seeded weights and rows it is handed;
* ``bench/references/<scorer>.py``: the scorer's plain reference forward;
* ``bench/traffic/<traffic>.json``: the mix's parameters, and the
  ``driver`` (loop shape) that reads them;
* ``bench/drivers/<driver>.py``: one loop shape: set-up, window, capture
  and check;
* ``bench/limits/<cell>.json``: the limit of each number ``correct``
  compares, with the readings it was set from;
* ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``:
  one reader per metric.

A new cell, mix, driver, scorer or metric is a new file found by its name.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List


@dataclasses.dataclass
class Spec:
    root: Path
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load(root: Path, cell: str) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, cell, names)]
    return Spec(
        root=root, name=cell,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{entry['traffic']}.json")
            .read_text()),
        limits=json.loads(
            (root / "bench" / "limits" / f"{cell}.json").read_text()),
        chips=int(entry["chips"]), end_to_end=e2e, per_layer=per_layer)


def module(root: Path, kind: str, name: str) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``, loaded from its file."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def read_metrics(root: Path, kind: str, metrics: List[dict],
                 run) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read; a reader that finds nothing returns None."""
    out = {}
    for m in metrics:
        value = module(root, kind, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
