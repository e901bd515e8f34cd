#!/usr/bin/env python3
"""Time one cell's traced window by the program's named scopes.

    python3 chipbench/scoped.py --workload granite.train4k.dp1 --seed 7 --seconds 10

Sets up as ``chipbench/run.py`` does (the same program, state, feed and
set-up steps), records the measured window with the profiler, reduces
the trace as a ``--trace 1`` run does, then compiles the step again
(from ``.bench_cache``) for its HLO text and joins the two
(``harness/scopes.py``). Logs the join to standard error and prints one
JSON line: the per-step numbers of ``scopes.json``'s metrics, the time
by phase, by scope and by the two together, the idle split, and the two sums that check the
join (phases over busy time; idle inside and between steps over the
window's idle). The reference is not run, so nothing is checked for
``correct``. ``--keep DIR`` writes the trace and the HLO text (less its
source-location tables) into DIR; ``--test-cell`` takes the cell from
``chipbench/tests/cells.py``.
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--test-cell", action="store_true")
    return ap.parse_args(argv)


def hlo_text(prog, cell):
    """The compiled step's HLO text, compiled as the window ran it."""
    import jax
    import jax.numpy as jnp
    sds = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    state = jax.tree.map(sds, prog.state_shape, prog.state_sharding)
    mix = cell["traffic"]
    shape = (mix["ranks"] * mix["seqs_per_rank"], mix["seq_len"])
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=prog.batch_sharding[k])
             for k in ("tokens", "labels")}
    return prog.step.lower(state, batch).compile().as_text()


def main(argv=None, require_tpu=True):
    args = parse(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import run
    if require_tpu:
        run.use_cache()
    import jax
    from chipbench.harness import cell as cell_lib, program, scopes, spec, trace
    from chipbench.harness.traffic import LMTraffic
    if args.test_cell:
        from chipbench.tests import cells
        cell = cells.cell(args.workload)
    else:
        cell = spec.cell(args.workload)
    devices = cell_lib.devices_for(cell["chips"], require_tpu)
    prog = program.Program(cell, devices)
    mix = LMTraffic(cell["traffic"], cell["config"]["model"]["vocab_size"], args.seed)
    state, _ = cell_lib.program_readings(prog, mix, program.seed_key(args.seed))

    shutil.rmtree(cell_lib.TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(cell_lib.TRACE_DIR)
    state, steps, elapsed, _ = cell_lib.window(prog, mix, state, args.seconds,
                                               cell_lib.SETUP_STEPS)
    jax.profiler.stop_trace()
    del state

    t0 = time.perf_counter()
    paths = glob.glob(os.path.join(cell_lib.TRACE_DIR, "**", "*.xplane.pb"), recursive=True)
    path = max(paths, key=os.path.getmtime)
    ops, spans = trace.load(path)
    red = trace.reduce(ops, spans, spec.names(), len(devices))
    t1 = time.perf_counter()
    text = hlo_text(prog, cell)
    t2 = time.perf_counter()
    joined = scopes.join(ops, scopes.load_modules(path), spans, text, len(devices))
    t3 = time.perf_counter()
    for line in scopes.log_lines(joined, steps):
        cell_lib.log(line)
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
        stem = os.path.join(args.keep, args.workload)
        shutil.copy(path, stem + ".scoped.xplane.pb")
        with gzip.open(stem + ".scoped.hlo.txt.gz", "wt") as f:
            f.write(scopes.without_tables(text))
    shutil.rmtree(cell_lib.TRACE_DIR, ignore_errors=True)

    idle_share = spec.metric_reader("device_idle_share").read({"trace": red})
    window_idle = idle_share / 100 * red["window_s"]
    per_ms = lambda d: {k: 1e3 * v / steps for k, v in d.items()}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "steps": steps,
        "tokens_per_s": steps * mix.tokens_per_step / elapsed,
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "device_idle_share": idle_share,
        "metrics": scopes.per_step(joined, steps),
        "phase_ms_per_step": per_ms(joined["phase_s"]),
        "scope_ms_per_step": per_ms(joined["scope_s"]),
        "phase_scope_ms_per_step": per_ms(joined["phase_scope_s"]),
        "joined_share": joined["joined_share"], "scoped_share": joined["scoped_share"],
        "unscoped_ops": joined["unscoped_ops"], "unmatched_s": joined["unmatched_s"],
        "idle_between_ms_per_step": 1e3 * joined["idle_between_s"] / steps,
        "idle_in_step_by_scope_ms": per_ms(joined["idle_in_step_by_scope"]),
        "in_step_gaps": joined["in_step_gaps"],
        "phase_sum_over_busy": sum(joined["phase_s"].values()) / red["busy_s"],
        "idle_sum_over_window_idle": (joined["idle_in_step_s"] + joined["idle_between_s"])
        / window_idle if window_idle else None,
        "reduce_s": t1 - t0, "hlo_compile_s": t2 - t1, "join_s": t3 - t2,
        "breakdown": red["breakdown"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
