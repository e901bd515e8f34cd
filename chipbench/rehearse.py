#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload mamba2.train4k.dp1

Builds the program's step as a run does, on the devices of a described
``v5e:2x2`` topology, compiles it at the cell's sizes and prints
``compiled.memory_analysis()`` and whether the Pallas kernels are in the
program (``tpu_custom_call``). Nothing runs; a compile that passes is
not a chip run.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from chipbench.harness import program as pl, spec

    cell = spec.cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    pl.import_program()
    from repro.kernels import ops
    ops._on_tpu = lambda: True          # the described chip takes the kernels
    prog = pl.Program(cell, list(topo.devices))
    sds = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    state = jax.tree.map(sds, prog.state_shape, prog.state_sharding)
    mix = cell["traffic"]
    rows = mix["ranks"] * mix["seqs_per_rank"]
    batch = {k: jax.ShapeDtypeStruct((rows, mix["seq_len"]), jax.numpy.int32,
                                     sharding=prog.batch_sharding[k])
             for k in ("tokens", "labels")}
    compiled = prog.step.lower(state, batch).compile()
    print(compiled.memory_analysis())
    print("tpu_custom_call in program:", "tpu_custom_call" in compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
