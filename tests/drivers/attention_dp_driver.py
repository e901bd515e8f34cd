"""Runs the train step on a 2-rank data-parallel mesh (``data`` manual,
``model`` 1 left auto) twice per aggregator: once with attention on the
blockwise path, as the CPU takes it, and once with the dispatch told it
is on a TPU, so that every layer's attention goes through the fused
kernel (``kernel_attention``, in Pallas's TPU interpreter) inside the
step's manual region, its nested per-device region, the layer scan, the
rematerialisation and the backward pass.

Prints one JSON line: per aggregator and path, the attention calls traced
into each path and the losses of three steps, and the largest gap between
the two paths' changes to the weights, relative to the blockwise path's
largest change. The optimizer is momentum, linear in the gradients, so
that gap is the gradients' own. Run with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``; the aggregators
are the arguments (default ``dense compressed compressed_rs``)."""
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from chipbench.harness import program  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "tests", "data", "configs", "tiny-dense32.json")
RANKS, SEQS, SEQ_LEN, STEPS = 2, 2, 256, 3
# the kernel's head size (64) over 4 heads and 2 KV heads; the smallest
# length it takes in two 128-row blocks is 256, which it runs as one block
MODEL = {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2}


def run(aggregator, kernel):
    from repro.models import layers as L
    calls = {"kernel": 0, "blockwise": 0}
    saved = {n: getattr(L, n) for n in
             ("attention_kernel_blocks", "kernel_attention", "blockwise_attention")}

    def counted(name, fn):
        def wrapped(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapped

    L.kernel_attention = counted("kernel", saved["kernel_attention"])
    L.blockwise_attention = counted("blockwise", saved["blockwise_attention"])
    if kernel:
        L.attention_kernel_blocks = (
            lambda sq, skv, hd, off, backend, auto:
            saved["attention_kernel_blocks"](sq, skv, hd, off, "tpu", auto))
    try:
        with open(CONFIG) as f:
            cfg = copy.deepcopy(json.load(f))
        cfg["model"].update(MODEL)
        cfg["train"]["aggregator"] = aggregator
        cfg["train"]["optimizer"].update(kind="momentum", lr=1e-2, warmup_steps=0,
                                         grad_clip=0.0)
        mix = {"ranks": RANKS, "seqs_per_rank": SEQS, "seq_len": SEQ_LEN}
        prog = program.Program({"config": cfg, "traffic": mix}, jax.devices()[:RANKS])
        state, start = prog.initial_state(jax.random.PRNGKey(7))
        tokens = jax.random.randint(jax.random.PRNGKey(8), (RANKS * SEQS, SEQ_LEN + 1),
                                    0, cfg["model"]["vocab_size"], jnp.int32)
        batch = prog.put({"tokens": tokens[:, :-1], "labels": tokens[:, 1:]})
        losses = []
        with pltpu.force_tpu_interpret_mode(kernel):
            for _ in range(STEPS):
                state, metrics = prog.step(state, batch)
                losses.append(float(metrics["loss"]))
        change = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                              jax.device_get(state.params), start)
        return calls, losses, change
    finally:
        for n, fn in saved.items():
            setattr(L, n, fn)


def main():
    if len(jax.devices()) < RANKS:
        raise SystemExit("needs XLA_FLAGS=--xla_force_host_platform_device_count=2")
    out = {}
    for aggregator in sys.argv[1:] or ("dense", "compressed", "compressed_rs"):
        b_calls, b_loss, b_change = run(aggregator, kernel=False)
        k_calls, k_loss, k_change = run(aggregator, kernel=True)
        leaves = list(zip(jax.tree.leaves(k_change), jax.tree.leaves(b_change)))
        gap = (max(float(np.max(np.abs(k - b))) for k, b in leaves)
               / max(float(np.max(np.abs(b))) for _, b in leaves))
        out[aggregator] = {"blockwise": {"calls": b_calls, "loss": b_loss},
                           "kernel": {"calls": k_calls, "loss": k_loss},
                           "param_gap": gap}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
