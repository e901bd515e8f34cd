"""Every configuration file's widths match the program's registry entry,
and the benchmark's files are consistent with BENCHMARK.json."""

import dataclasses
import json
import os

import pytest

from chipbench.harness import program, spec

program.import_program()
from repro.configs import ARCHS  # noqa: E402

BENCH = spec.benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
CONFIG_DIR = os.path.join(spec.BENCH_DIR, "configs")
FILES = sorted(f[:-5] for f in os.listdir(CONFIG_DIR) if f.endswith(".json"))


def load(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        return entry, json.load(f)


@pytest.mark.parametrize("name", FILES)
def test_widths_match_registry(name):
    """Every configuration file, in a cell or kept for a later one."""
    with open(os.path.join(CONFIG_DIR, name + ".json")) as f:
        cfg = json.load(f)
    fam = spec.family(cfg)
    reg = ARCHS[cfg["program"]["arch"]].model
    built = dataclasses.replace(reg, **fam.model_fields(cfg["model"], reg))
    for field in fam.WIDTH_FIELDS:
        assert getattr(built, field) == getattr(reg, field), field


@pytest.mark.parametrize("name", CONFIGS)
def test_file_states_its_cut(name):
    entry, cfg = load(name)
    assert cfg["name"] == name and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["reduced"]) == set(cfg["published"])
    for key in cfg["reduced"]:
        assert cfg["model"][key] != cfg["published"][key]
    assert cfg["deployment"] and cfg["departures"] and cfg["assumed"]


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    cell = spec.cell(w["name"])
    assert cell["traffic"]["ranks"] == w["chips"]
    assert cell["limits"]
    for k, lim in cell["limits"].items():
        assert k in ("loss_gap", "grad_norm_gap", "change_norm_gap")
        assert lim["limit"] > 0
    for m in cell["per_layer"]:
        reader = spec.metric_reader(m["name"])
        assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
