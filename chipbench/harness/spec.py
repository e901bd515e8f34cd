"""Finds a cell's files by name.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) joins one
configuration, found through the ``configs`` entry of the same name,
with one traffic mix, ``chipbench/traffic/<traffic>.json``. The limits
of its correctness comparison are in ``chipbench/limits/<cell>.json``;
each per-layer metric is ``chipbench/metrics/<metric>.py``. Adding a
cell, a configuration, a mix or a metric is adding files and entries.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from e


def benchmark():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name, bench=None, data_dir=BENCH_DIR):
    """Everything a run of cell ``name`` needs, as plain dicts. ``bench``
    (the parsed ``BENCHMARK.json``) and ``data_dir`` (where ``traffic/``
    and ``limits/`` are) default to the repository's own."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _load(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(data_dir, "traffic", w["traffic"] + ".json"))
    if traffic["ranks"] != w["chips"]:
        raise SpecError(f"{name}: traffic {w['traffic']} has {traffic['ranks']} "
                        f"ranks, the cell asks for {w['chips']} chips")
    limits = _load(os.path.join(data_dir, "limits", name + ".json"))
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"name": name, "chips": w["chips"], "config": cfg,
            "traffic": traffic, "limits": limits, "per_layer": per_layer,
            "end_to_end": end_to_end}


def family(cfg):
    return importlib.import_module(f"chipbench.families.{cfg['family']}")


def metric_reader(name):
    return importlib.import_module(f"chipbench.metrics.{name}")


def peaks(kind):
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise SpecError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table["devices"][kind]


def names():
    return _load(os.path.join(BENCH_DIR, "names.json"))
