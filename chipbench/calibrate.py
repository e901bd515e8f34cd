#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness check are set from.

    python3 chipbench/calibrate.py --workload granite.train4k.dp1 \
        --seeds 11,12,...,22 --control-seeds 11,12,13

For each seed of ``--seeds``: the program's set-up steps on the timed
path and the reference's, compared as a run compares them (the lower
readings). For each seed of ``--control-seeds``, the same comparison of
the reference against stand-ins put in the program's place: the control
(the reference with float8 products) and the reference with each fault
the cell can have planted in it (half of the microbatches left out; on
more than one rank, the exchange between chips left out). A state left
unchanged reads 1 by construction and is not run. Each reading is one
JSON line on standard output; the last line sums them up: per number,
the largest program reading and the smallest reading of each stand-in.
Needs the chips the cell asks for; no measured window is run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None, require_tpu=True, cell_spec=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax
    from chipbench import run
    if require_tpu:
        run.use_cache()
    from chipbench.harness import cell as cl, program as pl, spec
    from chipbench.harness.traffic import LMTraffic

    cell = cell_spec or spec.cell(args.workload)
    devices = cl.devices_for(cell["chips"], require_tpu)
    prog = pl.Program(cell, devices)
    vocab = cell["config"]["model"]["vocab_size"]
    ref = cl.reference(cell, devices)
    stand_ins = {"control": (cl.reference(cell, devices, mode="fp8"), None),
                 "half_batch": (ref, "half_batch"), "altered": (ref, "altered")}
    if cell["chips"] > 1:
        stand_ins["no_exchange"] = (ref, "no_exchange")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    summary = {}

    def emit(seed, variant, readings, ref):
        numbers = cl.compare(readings, ref)
        print(json.dumps({"seed": seed, "variant": variant, "numbers": numbers,
                          "detail": cl.detail(readings, ref)}), flush=True)
        agg = summary.setdefault(variant, {})
        pick = max if variant == "program" else min
        for k, v in numbers.items():
            agg[k] = pick(agg.get(k, v), v)

    for seed in seeds + [s for s in controls if s not in seeds]:
        mix = LMTraffic(cell["traffic"], vocab, seed)
        key = pl.seed_key(seed)
        t0 = time.perf_counter()
        first = jax.device_get(prog.draw(key))
        r_ref = cl.reference_readings(ref, mix, first)
        t_ref = time.perf_counter() - t0
        if seed in seeds:
            state, r_prog = cl.program_readings(prog, mix, key)
            del state
            gc.collect()
            emit(seed, "program", cl.with_change(r_prog), r_ref)
        if seed in controls:
            for name, (tr, fault) in stand_ins.items():
                emit(seed, name, cl.reference_readings(tr, mix, first, fault), r_ref)
        cl.log(f"seed {seed}: reference {t_ref:.1f} s, total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"summary": summary,
                      "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
