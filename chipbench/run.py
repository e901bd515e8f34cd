#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload granite.train4k.dp1 --seed 7 \
        --seconds 10 --trace 0

From the root of a checkout that holds the program (``src/repro``) and
``BENCHMARK.json``. Builds the cell's configuration and traffic from the
seed, sets up, measures for ``--seconds``, checks what the timed path
computed against the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device`` (and ``breakdown`` when traced), and ``compared``, each
compared number beside its limit, which also ends standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result. JAX's compilation cache is kept in
``<checkout>/.bench_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache():
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: with a size limit JAX keeps an access-time file per
    # entry, and an entry found without one fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None, require_tpu=True, tamper=None, cell_spec=None,
         t_start=T_START):
    """``require_tpu``, ``tamper`` and ``cell_spec`` are for the tests,
    which drive a run on the CPU at a small size (``cell.run``)."""
    args = parse(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if require_tpu:
        use_cache()
    from chipbench.harness import cell, spec
    try:
        result, compared = cell.run(args.workload, args.seed, args.seconds,
                                    bool(args.trace), t_start,
                                    require_tpu=require_tpu, tamper=tamper,
                                    cell=cell_spec)
    except (cell.NoChip, spec.SpecError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for k, c in compared.items():
        print(f"compared {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
