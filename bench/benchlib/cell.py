"""A cell of ``BENCHMARK.json`` and the files it is found by.

Everything that belongs to one configuration, one traffic mix, one cell
or one metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives:

    bench/configs/<config>.json    the configuration as it is run
    bench/configs/<config>.py      its weights and plain reference
    bench/traffic/<traffic>.json   the traffic mix
    bench/cells/<workload>.json    what the correctness check samples,
                                   and the limit of each number compared
    bench/metrics/<metric>.py      the reader of one metric

So a cell, a mix, a model or a metric is added by adding files and
entries, with no edit to the harness.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    source: str
    read: Callable            # read(run) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict              # bench/configs/<config>.json
    model: object             # bench/configs/<config>.py
    mix: dict                 # bench/traffic/<traffic>.json
    check: dict               # bench/cells/<workload>.json
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def _listed(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def _metric(entry: dict, bench_dir: str) -> Metric:
    mod = load_module(os.path.join(bench_dir, "metrics",
                                   entry["name"] + ".py"),
                      "bench_metric_" + entry["name"].replace(".", "_"))
    return Metric(entry["name"], entry["unit"], entry["source"], mod.read)


def load_cell(workload: str, bench_dir: str = BENCH_DIR,
              spec: Dict = None) -> Cell:
    """The cell named ``workload`` with its files; ``spec`` is the parsed
    ``BENCHMARK.json`` (read from the repository root if not given)."""
    if spec is None:
        spec = read_json(os.path.join(os.path.dirname(bench_dir),
                                      "BENCHMARK.json"))
    wl = [w for w in spec["workloads"] if w["name"] == workload]
    if not wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    cfg_dir = os.path.join(bench_dir, "configs")
    return Cell(
        name=workload, chips=int(wl["chips"]),
        config_name=wl["config"], traffic_name=wl["traffic"],
        config=read_json(os.path.join(cfg_dir, wl["config"] + ".json")),
        model=load_module(os.path.join(cfg_dir, wl["config"] + ".py"),
                          "bench_config_" + wl["config"].replace("-", "_")),
        mix=read_json(os.path.join(bench_dir, "traffic",
                                   wl["traffic"] + ".json")),
        check=read_json(os.path.join(bench_dir, "cells", workload + ".json")),
        end_to_end=[_metric(m, bench_dir) for m in spec["end_to_end"]
                    if _listed(m, workload)],
        per_layer=[_metric(m, bench_dir) for m in spec["per_layer"]
                   if _listed(m, workload)])
