"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix, and each of its metrics
is read by a file of its own.  A new cell, configuration, mix, kind,
reference or metric is a new file and a new entry, never an edit:

* the configuration is ``bench/configs/<config>.json``.  ``deploy.py``
  builds the program's compressor, store, service and gateway from its
  keys, and its optional ``"program"`` block passes further keyword
  arguments to those constructors by the program's own parameter names;
* the configuration's ``"reference"`` (default ``hybrid``) names its
  plain reference ``bench/reference/<name>.py``, which offers
  ``encoder(config)``: an object whose ``encode(text)`` gives the
  reference's token ids of a text;
* the mix is ``bench/traffic/<traffic>.json``, whose ``kind`` names its
  generator ``bench/traffic/kinds/<kind>.py``.  A kind offers
  ``prepare`` and ``drive`` (run in each load-generator process) and
  ``put_text`` and ``put_chars`` (a put's text and size made again from
  its ident, for the check).  It may offer two hooks, run in the
  harness's process: ``setup(params, config, seed, seconds, port)``,
  after the gateway starts and before the generators are released,
  through the gateway's own ops and inside ``setup_s``, which returns
  ``{"records": [...], "go": {...}}``: the requests it made (checked as
  any other) and fields for the generators' ``go``; and
  ``check(ctx, encoder)``, after the window, which returns further exact
  counts, each compared with the limit 0 beside the harness's own checks
  of acknowledged puts;
* each metric is read by ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]      # this cell's end-to-end metrics
    per_layer: List[dict]       # this cell's per-layer metrics


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def traffic(name: str) -> dict:
    doc = load_json(BENCH / "traffic" / f"{name}.json")
    doc["name"] = name
    return doc


def kind_module(kind: str) -> ModuleType:
    return load_module(BENCH / "traffic" / "kinds" / f"{kind}.py")


def reference_module(config: dict) -> ModuleType:
    return load_module(BENCH / "reference"
                       / f"{config.get('reference', 'hybrid')}.py")


def metric_module(name: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{name}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    doc = load_json(bench_file)
    found = [w for w in doc["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in {bench_file.name}; have "
                       f"{sorted(w['name'] for w in doc['workloads'])}")
    w = found[0]
    (cfg_entry,) = [c for c in doc["configs"] if c["name"] == w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    config["name"] = w["config"]
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=traffic(w["traffic"]),
        end_to_end=[m for m in doc["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in doc["per_layer"] if _applies(m, name)])
