"""Small cells for driving whole runs on the CPU.

The four-rank cell keeps its weights in float32: XLA's CPU backend
fails to compile the four-device compressed step with bfloat16
parameters (``Invalid binary instruction opcode copy``), which the TPU
compiles and runs."""

import json
import os

from chipbench.harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = {
    "tiny-dense.dp1": ("tiny-dense", "tiny.dp1", 1),
    "tiny-dense32.dp4": ("tiny-dense32", "tiny.dp4", 4),
    "tiny-ssm.dp1": ("tiny-ssm", "tiny.dp1", 1),
}


def bench():
    real = spec.benchmark()
    return {
        "configs": [{"name": c, "file": os.path.relpath(
            os.path.join(DATA, "configs", c + ".json"), spec.ROOT)}
            for c in ("tiny-dense", "tiny-dense32", "tiny-ssm")],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": k}
                      for n, (c, t, k) in CELLS.items()],
        "end_to_end": real["end_to_end"],
        "per_layer": [dict(m, workloads=list(CELLS)) if "workloads" in m else m
                      for m in real["per_layer"]],
    }


def cell(name):
    return spec.cell(name, bench=bench(), data_dir=DATA)


def write_limits(name, limits):
    with open(os.path.join(DATA, "limits", name + ".json"), "w") as f:
        json.dump(limits, f, indent=2)
