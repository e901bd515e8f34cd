"""Compiles the train step of the benchmark's tiny configurations and
prints, per case, the names found on the ``op_name`` stacks of the
compiled HLO (``jvp(attention)`` counts as ``attention``) and which of
JAX's phase marks appear. One JSON line on stdout; run with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.

Cases: ``tiny-dense`` and ``tiny-ssm`` on one rank (dense aggregation),
and ``tiny-dense32`` on a 2-device mesh through the ``compressed`` and
``compressed_rs`` wires, which run the codec's stages."""
import copy
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.harness import program  # noqa: E402

CONFIGS = os.path.join(ROOT, "chipbench", "tests", "data", "configs")
CASES = {
    "tiny-dense": ("tiny-dense", 1, None),
    "tiny-ssm": ("tiny-ssm", 1, None),
    "tiny-dense32.dp2.compressed": ("tiny-dense32", 2, "compressed"),
    "tiny-dense32.dp2.compressed_rs": ("tiny-dense32", 2, "compressed_rs"),
}
MARKS = ("jvp(", "transpose(", "rematted_computation")


def compiled_text(config, ranks, aggregator):
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cfg = json.load(f)
    if aggregator:
        cfg = copy.deepcopy(cfg)
        cfg["train"]["aggregator"] = aggregator
    mix = {"ranks": ranks, "seqs_per_rank": 2, "seq_len": 32}
    prog = program.Program({"config": cfg, "traffic": mix}, jax.devices()[:ranks])
    batch = {k: jax.ShapeDtypeStruct((2 * ranks, 32), jnp.int32,
                                     sharding=prog.batch_sharding[k])
             for k in ("tokens", "labels")}
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                         prog.state_shape, prog.state_sharding)
    return prog.step.lower(state, batch).compile().as_text()


def unwrap(component):
    """``transpose(jvp(attention))`` -> ``attention``."""
    while m := re.fullmatch(r"[\w.\-]+\((.*)\)", component):
        component = m.group(1)
    return component


def found(text):
    names = set(re.findall(r'op_name="((?:[^"\\]|\\.)*)"', text))
    stack = {unwrap(c) for n in names for part in n.split(";") for c in part.split("/")}
    marks = [m for m in MARKS if any(m in n for n in names)]
    forward = any("jvp(" in n and "transpose(" not in n
                  and "rematted_computation" not in n for n in names)
    return {"stack": sorted(stack), "marks": marks + (["forward"] if forward else [])}


def main():
    if len(jax.devices()) < 2:
        raise SystemExit("needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
    print(json.dumps({case: found(compiled_text(*args)) for case, args in CASES.items()}))


if __name__ == "__main__":
    main()
