"""Find a cell's parts by name: ``BENCHMARK.json`` at the checkout's root
names the cell's configuration and traffic mix; the mix is
``chipbench/traffic/<traffic>.json``, the configuration the ``file`` its
entry names, a per-layer metric's reader ``chipbench/metrics/<name>.py``
and a cell's correctness limits ``chipbench/limits/<cell>.json``.  Adding
a cell, mix, configuration or metric adds files and entries; no code here
names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]
PKG = "chipbench"


def benchmark(root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict, name: str, root: Path = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / PKG / "traffic" / f"{name}.json")
                      .read_text())


def limits(cell_name: str, root: Path = ROOT) -> Dict:
    return json.loads((Path(root) / PKG / "limits" / f"{cell_name}.json")
                      .read_text())


def metrics(bench: Dict, kind: str, cell_name: str) -> List[Dict]:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics this cell
    reports: those without a ``workloads`` list, and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of ``chipbench/metrics/<name>.py``."""
    path = Path(root) / PKG / "metrics" / f"{name}.py"
    mod_name = f"{PKG}_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    sp = importlib.util.spec_from_file_location(mod_name, path)
    if sp is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
