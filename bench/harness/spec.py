"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a data file of its
own (``bench/configs/<config>.json`` through the configuration's ``file``
entry, ``bench/traffic/<traffic>.json``), and each per-layer metric is a
reader of its own (``bench/layer_metrics/<name>.py``).  The configuration
names its model family in ``program.family``: a module of its own
(``bench/families/<family>.py``) that maps the published keys onto the
program, and supplies the plain reference and the operation counts.  Adding
a cell, or a model of a new family, means adding files and entries, never
editing this module.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
FAMILY_DIR = os.path.join(BENCH_DIR, "families")


class SpecError(RuntimeError):
    """The checkout does not hold what the named cell needs."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict           # the configuration file, as run
    family: ModuleType     # bench/families/<program.family>.py
    traffic_name: str
    traffic: Dict          # the traffic file
    end_to_end: List[Dict]  # metric entries this cell reports with --trace 0
    per_layer: List[Dict]   # metric entries this cell reports with --trace 1


def _load_json(path: str) -> Dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, mod_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str) -> ModuleType:
    """The model family module ``<FAMILY_DIR>/<name>.py``: ``model_config``,
    ``forward_rows``, the readers' operation and byte counts, and optionally
    ``draw`` for the weights (``harness/weights.py``)."""
    path = os.path.join(FAMILY_DIR, f"{name}.py")
    if not re.fullmatch(r"\w+", name) or not os.path.isfile(path):
        raise SpecError(f"no model family {name!r}: "
                        f"{os.path.relpath(path, ROOT)}")
    return _load_module(path, "bench_family_" + name)


def _family(config: Dict, path: str) -> ModuleType:
    """The family a configuration file names; there is no default, so that
    no configuration is read as another family's."""
    family = config.get("program", {}).get("family")
    if not isinstance(family, str):
        raise SpecError(f"{os.path.relpath(path, ROOT)} names no "
                        f"program.family")
    return load_family(family)


def _applies(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # no list: reported wherever the end-to-end metric it moves is
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(name: str) -> Cell:
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config_path = os.path.join(ROOT, centry["file"])
    config = _load_json(config_path)
    family = _family(config, config_path)
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, [])]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, family=family, traffic_name=w["traffic"],
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> Callable:
    """``read(record) -> float | None`` from ``bench/layer_metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader for per-layer metric {name!r}: "
                        f"{os.path.relpath(path, ROOT)}")
    mod_name = "layer_metric_" + name.replace(".", "_").replace("-", "_")
    return _load_module(path, mod_name).read


def peaks(device_kind: str) -> Dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/harness/peaks.json")
    return table["devices"][device_kind]
