"""Resolve one cell of ``BENCHMARK.json`` into the files it names.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each is found by name, with no table to edit:

- configuration ``<c>``: the ``file`` its ``configs`` entry gives;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- deployment of cell ``<w>`` (slots, context, offered rate, check limits):
  ``bench/cells/<w>.json``;
- per-layer metric ``<m>``: the reader ``bench/metrics/<m>.py``, a module
  with ``read(ctx) -> float | None``;
- a configuration's ``system`` ``<s>``: ``bench/systems/<s>.py``; its
  ``reference`` ``<r>``: ``bench/references/<r>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

__all__ = ["Cell", "ROOT", "load_benchmark", "resolve", "load_module",
           "cell_metrics"]

# bench/harness/spec.py -> the checkout root
ROOT = Path(__file__).resolve().parents[2]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    deployment: dict
    end_to_end: list[dict] = field(default_factory=list)
    per_layer: list[dict] = field(default_factory=list)
    root: Path = ROOT


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file {path} is missing")
    with open(path) as f:
        return json.load(f)


def cell_metrics(bench: dict, workload: str) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metrics that ``workload`` reports.

    An end-to-end metric without ``workloads`` is reported everywhere; a
    per-layer metric without ``workloads`` in every cell that reports the
    end-to-end metric it ``moves``."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (workload in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def resolve(workload: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _read_json(root / centry["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    deployment = _read_json(root / "bench" / "cells" / f"{workload}.json")
    e2e, per = cell_metrics(bench, workload)
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                deployment=deployment, end_to_end=e2e, per_layer=per,
                root=root)


def load_module(root: Path, kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    modname = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    mod = sys.modules.get(modname)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod
