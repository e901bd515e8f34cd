#!/usr/bin/env python3
"""Chip smoke test: drive the system's main path once on a TPU and check it.

    python chip_smoke.py                # one chip: attention, train, codec
    python chip_smoke.py --four-chips   # four chips: the data-parallel wires

One chip (the default):

- **attention** — granite's training attention at its published widths
  (B 1, S 4096, 32 heads over 8 KV heads, hd 64, bfloat16): the
  dispatching ``flash_attention`` must lower to the fused TPU kernel and
  agree with the blockwise path, forward output and q, k, v gradients,
  within ``ATTENTION_RTOL`` of the largest value.
- **train** — granite-3-2b (``configs/granite_3_2b.py``) at its published
  widths (d_model 2048, 32 heads, GQA kv 8, d_ff 8192, vocab 49155) cut
  to ``--n-layers`` (4) layers, seq_len 4096, global batch 8 with the
  registry's ``accum_steps=8``, random weights from ``--seed``. A few
  steps through ``repro.train.loop.run_training``, the code
  ``launch/train.py`` drives. Losses must be finite. At one data-parallel
  rank the step aggregates densely by design, so the codec gets its own
  phase.
- **codec** — one gradient of the same model and batch through the
  aggregator's pack stage (registry compression: ratio 0.1, top-k 0.04,
  error feedback), then one fused producer (``ops.encode_pack_quantize``)
  and one fused consumer (``ops.dequant_peel_unpack``) with
  ``use_pallas="always"`` over the whole stream, on the f32 and the
  fxp32 wire; the compiled program must hold the kernels
  (``tpu_custom_call``). Every peeled non-zero must come back to within
  rounding and every zero as zero; coordinates the registry sketch
  cannot peel are counted, not failed. The kernels and the jnp
  reference (``"never"``) must agree bit for bit (sketch, words, maxabs,
  values, residual) on a sample of the stream's blocks. The unpeeled
  count of the default run must not rise above the one recorded on a TPU
  v5e. The blocks within the peel capacity that still leave non-zeros
  are peeled again with more rounds, and the ones no round count peels
  are checked for a stopping set of two dense batch rows. Then the
  densest blocks, re-blocked for a sketch sized to peel them (the
  drivers' lossless setting), must peel whole.

Four chips (``--four-chips``, a ``data=4`` mesh, the same granite cut):
dense against ``compressed`` and ``compressed_rs`` in the lossless
setting (momentum optimizer, a sketch large enough for exact peeling),
losses agreeing within 1e-4 as ``tests/drivers/train_step_driver.py``
requires, then a few steps of the registry's own ``compressed`` config.

Everything runs in this one process. Lines before the last are smoke
output, not benchmark numbers. The last line is one JSON object naming
the device; it is printed only when every phase passed. Without a TPU,
or without the repository beside this file, the script exits non-zero
and prints no result. The persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "granite-3-2b"
SEQ_LEN = 4096
GLOBAL_BATCH = 8
LOSS_ATOL = 1e-4   # dense vs lossless compressed, per step (the driver's)


class SmokeFailure(Exception):
    """A phase's check failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def import_repo() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no src/repro beside {__file__}")
    sys.path.insert(0, src)


def granite(n_layers: int):
    """(arch spec, published granite config cut to ``n_layers``)."""
    from repro.configs import get_arch
    from repro.launch.train import select_model
    arch = get_arch(ARCH)
    return arch, select_model(arch, n_layers=n_layers)


def lossless_compression():
    """The train-step driver's lossless codec: a sketch twice the stream,
    enough for every coordinate of a dense gradient to peel."""
    from repro.core import CompressionConfig
    return CompressionConfig(ratio=2.0, lanes=512, rows=60, chunk_blocks=64)


def momentum_optimizer(steps: int):
    """A linear optimizer, so peel rounding stays at f32 epsilon."""
    from repro.train.optimizer import OptimizerConfig
    return OptimizerConfig(kind="momentum", lr=1e-2, warmup_steps=0,
                           total_steps=max(steps, 2), grad_clip=0.0)


def train(api, tc, mesh, steps: int, label: str, global_batch: int):
    """``steps`` steps of ``run_training``; returns the result."""
    import jax
    from repro.train.loop import run_training

    def step_log(line):
        log(f"{label} {line.removeprefix('[loop] ')}")

    res = run_training(api, tc, mesh, global_batch=global_batch,
                       seq_len=SEQ_LEN, steps=steps, log_every=1,
                       log_fn=step_log)
    check(len(res.losses) == steps, f"{label}: {len(res.losses)} steps ran")
    check(all(math.isfinite(x) for x in res.losses),
          f"{label}: non-finite loss {res.losses}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"{label} losses {res.losses} peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    return res


def phase_train(args, mesh):
    from repro.models import model_api
    arch, cfg = granite(args.n_layers)
    log(f"train: {ARCH} n_layers {cfg.n_layers} (published "
        f"{arch.model.n_layers}) d_model {cfg.d_model} heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} d_ff {cfg.d_ff} vocab {cfg.vocab} "
        f"seq_len {SEQ_LEN} global_batch {GLOBAL_BATCH} accum_steps "
        f"{arch.train.accum_steps} aggregator {arch.train.aggregator}")
    tc = dataclasses.replace(arch.train, seed=args.seed)
    api = model_api(cfg)
    res = train(api, tc, mesh, args.steps, "train", GLOBAL_BATCH)
    return {"api": api, "cfg": cfg, "tc": tc, "params": res.state.params}


def mean_gradient(api, cfg, tc, params, seed: int):
    """The step's gradient of one global batch, accumulated over the
    registry's microbatches."""
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import batch_fn

    batch = batch_fn(cfg, GLOBAL_BATCH, SEQ_LEN, seed=seed)(0)
    micro = {k: jnp.asarray(v).reshape((tc.accum_steps, -1) + v.shape[1:])
             for k, v in batch.items()}

    def grads(p, mbs):
        def body(acc, mb):
            g = jax.grad(lambda q: api.loss(q, mb, remat=tc.remat)[0])(p)
            return jax.tree.map(jnp.add, acc, g), None
        zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        acc, _ = jax.lax.scan(body, zero, mbs)
        return jax.tree.map(lambda g: g / tc.accum_steps, acc)

    return jax.jit(grads)(params, micro)


CODEC_OUTPUTS = ("sketch", "words", "maxabs", "values", "residual")
# The jnp reference peel is far slower than the kernel on the chip (about
# 28 ms a block), so the backends are compared on a sample of the
# stream: the densest blocks and an even spread of the rest.
PARITY_BLOCKS = 256
# The lossless check re-blocks this many of the densest blocks for a
# sketch sized to peel them whole.
LOSSLESS_BLOCKS = 32
# Non-zeros the registry codec leaves unpeeled in the default run
# (--n-layers 4 --steps 3 --seed 0) on a TPU v5e, the same on both wires.
# A rise fails the smoke.
UNPEELED_BOUND = {(4, 3, 0): 13_047_108}
# Rounds for the second peel of the blocks within capacity that the
# configured rounds leave unfinished.
PROBE_ROUNDS = 100


def codec_fn(ccfg, wire_dtype: str):
    """Jitted (xb (nb, G, c), ids (nb,), exponents (nb,) or None) ->
    (sketch, words, maxabs, values, residual): one fused producer, then
    one fused consumer, in the compressor's ``chunk_blocks`` chunks. On
    the fxp32 wire the producer quantizes the sketch with the given
    per-block exponents and the consumer dequantizes it."""
    import jax
    from repro.core.compressor import chunked_map
    from repro.kernels import ops
    from repro.net.fixedpoint import FixedPointWire

    fxp = ({} if wire_dtype == "f32" else
           {"mantissa_bits": FixedPointWire(workers=1).mantissa_bits})

    def fn(xb, ids, exp):
        def chunked(f, *arrays):
            return chunked_map(f, xb.shape[0], ccfg.chunk_blocks, *arrays)

        if not fxp:
            sk, words, mx = chunked(
                lambda x, i: ops.encode_pack_quantize(x, i, ccfg), xb, ids)
            values, residual = chunked(
                lambda s, w, i: ops.dequant_peel_unpack(s, w, i, ccfg),
                sk, words, ids)
            return sk, words, mx, values, residual
        sk, words, mx = chunked(
            lambda x, i, e: ops.encode_pack_quantize(
                x, i, ccfg, exponents=e, **fxp), xb, ids, exp)
        values, residual = chunked(
            lambda s, w, i, e: ops.dequant_peel_unpack(
                s, w, i, ccfg, exponents=e, **fxp), sk, words, ids, exp)
        return sk, words, mx, values, residual

    return jax.jit(fn)


def run_codec(ccfg, wire_dtype, backend, xb, ids, exp, label):
    """Compile and run :func:`codec_fn` on ``backend``; the kernels'
    program must hold Mosaic custom calls."""
    import jax
    c = dataclasses.replace(ccfg, use_pallas=backend)
    t0 = time.perf_counter()
    compiled = codec_fn(c, wire_dtype).lower(xb, ids, exp).compile()
    out = jax.block_until_ready(compiled(xb, ids, exp))
    log(f"{label} use_pallas={backend}: {xb.shape[0]} blocks compiled and "
        f"ran in {time.perf_counter() - t0:.1f} s")
    if backend == "always":
        check("tpu_custom_call" in compiled.as_text(),
              f"{label}: no tpu_custom_call in the compiled program")
    return out


def check_equal(label, outs_a, outs_b, what):
    import jax.numpy as jnp
    for name, a, b in zip(CODEC_OUTPUTS, outs_a, outs_b):
        same = bool(jnp.array_equal(a, b))
        log(f"{label} {name}: {what} {same}")
        check(same, f"{label}: {name} differs ({what})")


def check_recovery(label, xb, values, residual) -> int:
    """The peel's contract on every coordinate: a zero stays zero, only a
    non-zero can be left unpeeled (flagged in ``residual``), and every
    peeled non-zero comes back to within rounding. Returns the number
    left unpeeled."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(x, v, r):
        nz, left = x != 0, r != 0
        peeled = nz & ~left
        return (jnp.sum(nz), jnp.sum(left), jnp.sum(peeled & (v == x)),
                jnp.all(nz | (v == 0)), jnp.all(nz | ~left),
                jnp.max(jnp.where(peeled, jnp.abs(v - x), 0.0)),
                jnp.max(jnp.abs(x)))

    nnz, unpeeled, exact, zeros_ok, left_ok, err, scale = (
        x.item() for x in stats(xb, values, residual))
    log(f"{label}: {unpeeled} of {nnz} non-zeros left unpeeled; of the "
        f"peeled, {exact} bit-exact, max |error| {err:.3e} against max "
        f"|x| {scale:.3e}")
    check(zeros_ok, f"{label}: a zero coordinate came back non-zero")
    check(left_ok, f"{label}: a zero coordinate flagged unpeeled")
    check(err <= RECOVERY_RTOL * scale,
          f"{label}: peeled error {err} above {RECOVERY_RTOL} x max|x| "
          f"{scale}")
    return unpeeled


# Peeling subtracts recovered values from f32 cell sums, so a value that
# shared a cell is recovered to within the rounding of that sum: a few
# f32 epsilons (2**-23) of the largest value in the cell. The fxp32 wire
# adds its quantization step, 2**-30 of the bucket's largest cell at one
# worker. 2**-16 leaves two orders of magnitude for that, and still
# catches any mis-peeled coordinate (an error of the order of |x|).
RECOVERY_RTOL = 2.0 ** -16


def twin_dense_rows(xb, ccfg):
    """(n, G, c) blocks -> (n,) bool: the block holds two batch rows that
    are non-zero in every lane and hash to the same three sketch rows.
    Every cell such a pair reaches then holds two of its values, so no
    round of peeling ever finds a pure cell among them."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sketch import plan_tables
    tbl, _ = plan_tables(ccfg)
    per = ccfg.rows // 3
    cls = sum((tbl[:, j] - j * per) * per ** j for j in range(3))
    onehot = jnp.asarray(np.eye(per ** 3, dtype=np.float32)[cls])
    dense = jnp.all(xb != 0, axis=2).astype(jnp.float32)       # (n, G)
    return np.asarray(jnp.any(dense @ onehot >= 2, axis=1))


def probe_unfinished(ccfg, xb, ids, per_block, left_blocks):
    """Peel the blocks within capacity that left non-zeros unpeeled again
    with :data:`PROBE_ROUNDS` rounds (kernel), and log how many the round
    cap explains and how many are stopping sets."""
    import jax.numpy as jnp
    import numpy as np
    sel = np.flatnonzero(np.asarray(left_blocks
                                    & (per_block <= ccfg.peel_capacity)))
    if sel.size == 0:
        return
    sel = jnp.asarray(sel, jnp.int32)
    label = f"codec f32 rounds {PROBE_ROUNDS}"
    out = run_codec(dataclasses.replace(ccfg, rounds=PROBE_ROUNDS), "f32",
                    "always", xb[sel], ids[sel], None, label)
    still = np.asarray(jnp.any(out[4] != 0, axis=(1, 2)))
    twins = twin_dense_rows(xb[sel], ccfg)
    nnz = np.asarray(per_block[sel])

    def median(mask):
        return f"{np.median(nnz[mask]):.0f}" if mask.any() else "none"

    log(f"{label}: of the {sel.size} blocks within capacity left "
        f"unfinished at {ccfg.rounds} rounds, {sel.size - still.sum()} "
        f"peel whole and {still.sum()} do not; {(still & twins).sum()} of "
        f"those hold two dense batch rows on the same sketch rows, "
        f"{(~still & twins).sum()} of the finished ones do; median "
        f"non-zeros a block {median(~still)} finished, {median(still)} not")


def sparsified_stream(api, cfg, tc, params, seed: int):
    """One gradient through the aggregator's pack stage (registry top-k
    and error feedback from a zero residual) -> ((nb, G, c) blocks,
    bucket plan)."""
    import jax
    from repro.core.aggregators import pack_stream
    from repro.core.bucketing import make_bucket_plan
    from repro.core.collectives import init_aggregation_state

    ccfg = tc.compression
    grads = mean_gradient(api, cfg, tc, params, seed)
    plan = make_bucket_plan(grads, ccfg)
    res0 = init_aggregation_state(grads, ccfg).residual
    buckets, _ = jax.jit(functools.partial(pack_stream, plan, cfg=ccfg))(
        grads, res0)
    return buckets.reshape(-1, ccfg.group, ccfg.lanes), plan


def phase_codec(args, trained):
    """``trained``: what :func:`phase_train` returns; its parameters are
    taken out of it, so they are freed once the gradient exists."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.config import GAMMA
    from repro.net.fixedpoint import FixedPointWire

    tc = trained["tc"]
    ccfg = tc.compression
    log(f"codec: ratio {ccfg.ratio} topk_ratio {ccfg.topk_ratio} "
        f"error_feedback {ccfg.error_feedback} G {ccfg.group} lanes "
        f"{ccfg.lanes} rows {ccfg.rows} bucket_bytes {ccfg.bucket_bytes}")
    xb, plan = sparsified_stream(trained["api"], trained["cfg"], tc,
                                 trained.pop("params"), args.seed)
    nb = xb.shape[0]
    ids = jnp.arange(nb, dtype=jnp.int32)
    per_block = jax.jit(lambda x: jnp.sum(x != 0, axis=(1, 2)))(xb)
    nnz = int(per_block.sum())
    cap = ccfg.peel_capacity
    log(f"codec: stream {plan.total} elems, {plan.n_buckets} buckets, "
        f"{nb} blocks, {nnz} non-zeros after top-k")
    log(f"codec: non-zeros per block median "
        f"{float(jnp.median(per_block)):.0f} max {int(per_block.max())}; "
        f"peel capacity {cap} (rows x lanes / {GAMMA}); "
        f"{int(jnp.sum(per_block > cap))} blocks above it")
    check(nnz > 0, "codec: the sparsified gradient is all zeros")

    def densest(k):
        return np.asarray(jax.lax.top_k(per_block, min(k, nb))[1])

    k = min(PARITY_BLOCKS // 2, nb)
    sample = jnp.asarray(np.unique(np.concatenate(
        [densest(k), np.linspace(0, nb - 1, k).astype(np.int64)])),
        jnp.int32)
    bound = UNPEELED_BOUND.get((args.n_layers, args.steps, args.seed))
    exp = None
    for wire_dtype in ("f32", "fxp32"):
        label = f"codec {wire_dtype}"
        full = run_codec(ccfg, wire_dtype, "always", xb, ids, exp, label)
        unpeeled = check_recovery(label, xb, full[3], full[4])
        left_blocks = jnp.any(full[4] != 0, axis=(1, 2))
        log(f"{label}: {int(left_blocks.sum())} blocks left non-zeros "
            f"unpeeled, {int(jnp.sum(left_blocks & (per_block <= cap)))} "
            f"of them within the peel capacity; {unpeeled / nnz:.6f} of "
            "the non-zeros go to the median estimate")
        if bound is not None:
            check(unpeeled <= bound, f"{label}: {unpeeled} non-zeros left "
                  f"unpeeled, above the recorded {bound}")
        if exp is None:
            probe_unfinished(ccfg, xb, ids, per_block, left_blocks)
        sub_exp = None if exp is None else exp[sample]
        outs = [run_codec(ccfg, wire_dtype, b, xb[sample], ids[sample],
                          sub_exp, f"{label} sample") for b in
                ("always", "never")]
        check_equal(f"{label} sample", *outs, "pallas == reference")
        check_equal(f"{label} sample", [o[sample] for o in full], outs[0],
                    "whole stream == sample")
        if exp is None:
            # The fxp32 wire's per-bucket shared exponents, from the
            # producer's per-block max |sketch| at one worker.
            nbpb = plan.blocks_per_bucket(ccfg)
            bucket_max = full[2].reshape(-1, nbpb).max(axis=1)
            exp = jnp.repeat(FixedPointWire(workers=1).exponents_from_maxabs(
                bucket_max), nbpb)
        del full, outs

    # The paper's lossless claim on the real stream: with a sketch sized
    # for them, the densest blocks peel whole.
    lcfg = lossless_compression()
    check(ccfg.block_elems % lcfg.block_elems == 0
          and ccfg.lanes == lcfg.lanes, "lossless re-blocking")
    xs = xb[densest(LOSSLESS_BLOCKS)].reshape(-1, lcfg.group, lcfg.lanes)
    lids = jnp.arange(xs.shape[0], dtype=jnp.int32)
    label = f"codec lossless (ratio {lcfg.ratio}, rows {lcfg.rows})"
    outs = [run_codec(lcfg, "f32", b, xs, lids, None, label)
            for b in ("always", "never")]
    check_equal(label, *outs, "pallas == reference")
    unpeeled = check_recovery(label, xs, outs[0][3], outs[0][4])
    check(unpeeled == 0, f"{label}: {unpeeled} non-zeros left unpeeled")


# The attention check: largest |kernel - blockwise| over the largest
# |blockwise|, for the output and each gradient. Both compute scores in
# float32 from bfloat16 q and k and round the output to bfloat16, so they
# differ by a few bfloat16 ulps of the largest value (each reads 3e-3 to
# 8e-3 against a float32 reference on a TPU v5e); a wrong mask, scale or
# head order reads of order 1.
ATTENTION_RTOL = 3e-2


def phase_attention(args):
    """Granite's training attention at its published widths (B 1, S 4096,
    32 heads over 8 KV heads, hd 64, bfloat16): the dispatching
    ``flash_attention`` must lower to the fused kernel on the chip and
    agree with the blockwise path, forward output and q, k, v gradients."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L

    _, cfg = granite(args.n_layers)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, w = (jax.random.normal(k, (1, SEQ_LEN, h, hd), jnp.bfloat16)
            for k in ks[:2])
    k, v = (jax.random.normal(k, (1, SEQ_LEN, kv, hd), jnp.bfloat16)
            for k in ks[2:])

    def value_and_grads(attn):
        def run(q, k, v, w):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(w)
        return jax.jit(run).lower(q, k, v, w).compile()

    kernel = value_and_grads(
        lambda q, k, v: L.flash_attention(q, k, v, True, cfg.q_block))
    check("splash_mha" in kernel.as_text(),
          "attention: flash_attention did not lower to the fused kernel")
    blockwise = value_and_grads(
        lambda q, k, v: L.blockwise_attention(q, k, v, True, cfg.q_block))
    got, want = kernel(q, k, v, w), blockwise(q, k, v, w)
    worst = 0.0
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        log(f"attention: {name} relative gap kernel vs blockwise {gap:.3e}")
        worst = max(worst, gap)
    check(worst <= ATTENTION_RTOL,
          f"attention: kernel vs blockwise gap {worst:.3e} > {ATTENTION_RTOL}")


def phase_four_chips(args, mesh):
    from repro.models import model_api
    from repro.parallel.sharding import ShardingProfile
    arch, cfg = granite(args.n_layers)
    api = model_api(cfg)
    base = dataclasses.replace(
        arch.train, optimizer=momentum_optimizer(args.steps),
        sharding=ShardingProfile(zero1=False), accum_steps=2,
        seed=args.seed)
    losses = {}
    for agg in ("dense", "compressed", "compressed_rs"):
        tc = dataclasses.replace(base, aggregator=agg)
        if agg != "dense":
            tc = dataclasses.replace(tc, compression=lossless_compression())
        losses[agg] = train(api, tc, mesh, args.steps, f"4chip {agg}",
                            GLOBAL_BATCH).losses
        del tc
    for agg in ("compressed", "compressed_rs"):
        diff = max(abs(a - b) for a, b in zip(losses[agg], losses["dense"]))
        log(f"4chip {agg} vs dense: max |loss difference| {diff:.3e}")
        check(diff <= LOSS_ATOL, f"4chip {agg} diverged from dense: "
              f"{losses[agg]} vs {losses['dense']}")
    # The registry's own compressed config (Adam, top-k 0.04 with error
    # feedback, accum_steps 8): 8 microbatches of one sequence per rank.
    tc = dataclasses.replace(arch.train, seed=args.seed)
    train(api, tc, mesh, args.steps, "4chip registry compressed",
          tc.accum_steps * mesh.shape["data"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel path")
    ap.add_argument("--n-layers", type=int, default=4,
                    help="granite depth cut (widths stay published)")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import_repo()
        import jax
        from repro.launch.cache import use_compile_cache
        from repro.launch.mesh import make_host_mesh

        log(f"compile cache {use_compile_cache()}")
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        log(f"device {json.dumps(device)}")
        check(device["platform"] == "tpu", "JAX finds no TPU")
        want = 4 if args.four_chips else 1
        check(device["count"] >= want,
              f"{want} chips needed, {device['count']} found")
        if args.four_chips:
            mesh = make_host_mesh()
            check(mesh.shape["data"] == 4,
                  f"four-chip mesh is {dict(mesh.shape)}")
            phase_four_chips(args, mesh)
        else:
            mesh = make_host_mesh()
            phase_attention(args)
            phase_codec(args, phase_train(args, mesh))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
