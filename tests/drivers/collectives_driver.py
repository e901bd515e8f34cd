"""Multi-device driver: run with XLA_FLAGS=--xla_force_host_platform_device_count=8.

Validates, on a (2, 2, 2) pod/data/model mesh:
  1. or_allreduce (ring + doubling) == numpy bitwise-or reduce
  2. compressed_all_reduce of a TP-sharded gradient pytree == mean of
     per-worker gradients (within fp tolerance), via the bucketed
     aggregator (nested shard_map packing where supported).
  3. bucketed compressed aggregation with topk_ratio + error_feedback
     matches the pre-bucketing per-leaf path BIT-FOR-BIT over 3 steps
     (residual roundtrip included; reference computed per leaf with the
     same sparsifier, dyadic values so every psum order is exact), and
     the overlap-pipelined schedule matches the fused one bitwise.
  4. the reduce-scatter aggregator (per-rank bucket peeling) matches the
     dense mean like the plain one.
  5. multi-axis hierarchical OR-AllReduce with a non-power-of-2 *inner*
     axis (a (2, 3) pod/data mesh) == numpy, on whichever wire this JAX
     leg takes (ring+doubling vs psum emulation), the explicit
     ring-then-doubling composition, and the chunked psum emulation ==
     unchunked bit-for-bit.
  6. or_reduce_scatter: every rank's chunk reassembles to the numpy OR
     reduce (power-of-2 and non-power-of-2 axes, single and multi axis,
     rank-major chunk order pinned against psum_scatter's).
  7. the native reduce-scatter wire (psum_scatter sketch + OR-RS bitmap,
     full-manual region) is bit-identical to
     the emulated psum+slice wire and to CompressedAggregator over 3
     error-feedback steps.
  8. the in-network tier (PR 4): tree_all_reduce (ppermute reduce-to-root
     + broadcast) == psum / numpy-OR on pow2 and non-pow2 axes for both
     topology kinds and on the no-ppermute fallback wire; compressed_innet
     with wire_dtype=f32 is bit-identical to CompressedAggregator over 3
     EF steps; with wire_dtype=fxp32 it equals BOTH the f32 path (dyadic
     values round-trip the fixed-point wire exactly) and an independent
     host-side replay of the documented codec roundtrip
     (shared-exponent quantize -> integer sum -> dequantize -> peel),
     for the flat and tor_spine topologies.
  9. the stream scheduler (PR 5): chunked wire grids — per-bucket and
     non-divisible AllReduce chunks, per-rank-aligned native-RS chunks
     (per-chunk psum_scatter/OR-RS), emulated-RS chunks, and innet
     switch-window chunks (f32 + fxp32) — are ALL bit-identical to the
     fused wire over 3 EF steps; dense ignores the knob; a grid that
     splits a per-rank RS boundary raises ValueError naming the
     constraint; tree_all_reduce's windowed mode == one-shot.
 10. the ZeRO-1 gather-skip: on a chunk grid aligned with the ZeRO-1
     slices the native-RS aggregator skips the recovered-chunk
     all_gather (pinned on the jaxpr), each rank's slice is bit-exact
     vs the full wire, off-slice values are zero, residuals identical.
 11. per-bucket wire plans (PR 6): mixed plans partitioning the 5-bucket
     EF stream across dense / compressed / native-RS / innet groups —
     executed by both the ``compressed`` strategy (explicit plan) and
     the ``auto`` strategy — are bit-identical to the fixed
     ``compressed`` run over 3 EF steps, outputs and residuals, at W=4
     over the (pod, data) axes (every wire is exact on dyadic values).
 12. the all-to-all exchange (PR 8): stacked (W, n) payloads routed
     slice-r-to-rank-r; the compressed permute wire (sketch add + bitmap
     OR merged in flight, ratio 2.5 = always-exact peel) equals the
     dense wire and the numpy per-destination sum bit-for-bit over 3
     steps — native single-axis ppermute lanes (W=2), the psum-emulated
     multi-axis wire (W=4 over pod x data), and a chunked
     (stream_chunks=2) lane grid.
"""
import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count=8 "
    "--xla_disable_hlo_passes=all-reduce-promotion")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.compat import make_mesh, shard_map
from repro.core import CompressionConfig
from repro.core.collectives import (
    or_allreduce, compressed_all_reduce, dense_all_reduce,
    init_aggregation_state)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rng = np.random.default_rng(0)

# ---- 1. OR-allreduce ------------------------------------------------
W = 4  # pod*data workers
words = rng.integers(0, 2**32, size=(W, 4096), dtype=np.uint32)
expect = np.bitwise_or.reduce(words, axis=0)

def or_fn(x):
    return or_allreduce(x, ("pod", "data"))

# lay the 4 distinct worker payloads over (pod,data); replicate over model
x = jnp.asarray(words.reshape(2, 2, 4096))
sh = NamedSharding(mesh, P("pod", "data", None))
got = jax.jit(shard_map(
    lambda a: or_fn(a[0, 0]),
    mesh=mesh, in_specs=P("pod", "data", None),
    out_specs=P(), axis_names={"pod", "data"}, check_vma=False,
))(jax.device_put(x, sh))
assert np.array_equal(np.asarray(got), expect), "OR-allreduce mismatch"
print("OK or_allreduce hierarchical")

# ring + doubling individually over one axis, in a full-manual region
# so the collective itself is what is tested.
words2 = rng.integers(0, 2**32, size=(2, 100_000), dtype=np.uint32)
from repro.core.collectives import or_allreduce_ring, or_allreduce_doubling
for name, fn in [("ring", or_allreduce_ring), ("doubling", or_allreduce_doubling)]:
    got2 = jax.jit(shard_map(
        lambda a, fn=fn: fn(a[0], "pod"),
        mesh=mesh, in_specs=P("pod", None), out_specs=P(),
        axis_names={"pod", "data", "model"}, check_vma=False,
    ))(jax.device_put(jnp.asarray(words2.reshape(2, 1, -1)[:, 0]),
                      NamedSharding(mesh, P("pod", None))))
    assert np.array_equal(np.asarray(got2), np.bitwise_or.reduce(words2, 0)), name
    print(f"OK or_allreduce_{name}")

# ---- 2. compressed_all_reduce on a TP-sharded pytree ----------------
cfg = CompressionConfig(ratio=0.25, rounds=10, lanes=512, chunk_blocks=64)
D, F = 256, 512
n_workers = 4


def make_grads(seed):
    r = np.random.default_rng(seed)
    def sparse(shape, frac=0.04):
        g = np.zeros(np.prod(shape), np.float32)
        idx = r.choice(g.size, size=int(g.size * frac), replace=False)
        g[idx] = r.normal(size=idx.size).astype(np.float32)
        return g.reshape(shape)
    return {"w1": sparse((D, F)), "w2": sparse((F, D)), "scale": sparse((D,), 0.1)}


per_worker = [make_grads(s) for s in range(n_workers)]
mean_ref = jax.tree.map(lambda *g: np.mean(g, axis=0), *per_worker)

specs = {"w1": P(None, "model"), "w2": P("model", None), "scale": P()}

# global arrays whose (pod,data) shard w is per_worker[w]
stacked = jax.tree.map(lambda *g: np.stack(g).reshape((2, 2) + g[0].shape), *per_worker)


def outer(grads_stacked):
    grads = jax.tree.map(lambda a: a[0, 0], grads_stacked)  # this worker's grads
    params_like = jax.tree.map(lambda a: a, grads)
    st = init_aggregation_state(params_like, cfg)
    agg, _ = compressed_all_reduce(grads, st, specs, mesh, cfg,
                                   dp_axes=("pod", "data"), tp_axes=("model",))
    return agg


in_specs = {"w1": P("pod", "data", None, None),
            "w2": P("pod", "data", None, None),
            "scale": P("pod", "data")}
# model placement is auto: apply via device_put sharding below
put_specs = {"w1": P("pod", "data", None, "model"),
             "w2": P("pod", "data", "model", None),
             "scale": P("pod", "data")}
out_specs = {"w1": P(), "w2": P(), "scale": P()}  # model placement is auto

put = jax.tree.map(
    lambda a, s: jax.device_put(jnp.asarray(a), NamedSharding(mesh, s)),
    stacked, put_specs, is_leaf=lambda x: isinstance(x, np.ndarray))

got = jax.jit(shard_map(outer, mesh=mesh, in_specs=(in_specs,),
                            out_specs=out_specs,
                            axis_names={"pod", "data"}, check_vma=False))(put)
got = jax.tree.map(np.asarray, got)
for k in ("w1", "w2", "scale"):
    ok = np.allclose(got[k], mean_ref[k], atol=1e-5)
    print(f"{'OK' if ok else 'FAIL'} compressed_all_reduce[{k}] maxerr={np.abs(got[k]-mean_ref[k]).max():.2e}")
    assert ok, k

# dense baseline for comparison
got_d = jax.jit(shard_map(
    lambda gs: dense_all_reduce(jax.tree.map(lambda a: a[0, 0], gs), ("pod", "data")),
    mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
    axis_names={"pod", "data"}, check_vma=False))(put)
for k in ("w1", "w2", "scale"):
    assert np.allclose(np.asarray(got_d[k]), mean_ref[k], atol=1e-6), k
print("OK dense_all_reduce baseline")

# ---- 3. bucketed top-k + EF == per-leaf path, bit-for-bit, 3 steps ---
# Pure-DP pytree (replicated specs) so the per-leaf reference below has
# exactly the shard-local view the aggregator sparsifies. Dyadic values
# (sign * 2^e) make every summation order exact, so bitwise equality
# checks the math. ratio=1.0 keeps peel capacity far above the top-k
# density: recovery is exact and the only "lossy" step is the
# sparsifier — which must be the seed's per-leaf one, bit-for-bit.
import dataclasses
from repro.core import topk as topk_lib
from repro.core.aggregators import make_aggregator
from repro.core.collectives import AggregationState

cfg_ef = CompressionConfig(ratio=1.0, lanes=128, rows=6, rounds=10,
                           chunk_blocks=8, topk_ratio=0.1, topk_exact=True,
                           error_feedback=True, bucket_bytes=2 * 768 * 4)
assert cfg_ef.block_elems == 768
ef_shapes = {"wa": (96, 40), "wb": (3000,), "wc": (11,)}
ef_specs = {k: P() for k in ef_shapes}


def dyadic_tree(seed):
    r = np.random.default_rng(seed)
    out = {}
    for k, sh in ef_shapes.items():
        n = int(np.prod(sh))
        g = np.zeros(n, np.float32)
        nz = max(1, int(n * 0.3))
        idx = r.choice(n, size=nz, replace=False)
        g[idx] = (r.choice([-1.0, 1.0], size=nz)
                  * np.exp2(r.integers(-2, 3, size=nz))).astype(np.float32)
        out[k] = g.reshape(sh)
    return out


def run_ef(overlap, name="compressed", rs_wire="auto", wire_plan=None,
           **overrides):
    cfg = dataclasses.replace(cfg_ef, overlap=overlap, rs_wire=rs_wire,
                              **overrides)
    # The region below takes every mesh axis manual, so declare it:
    # full-manual callers reassemble with a manual-axis all_gather.
    agg = make_aggregator(name, cfg, mesh, ("pod", "data"), (),
                          outer_manual=("pod", "data", "model"),
                          wire_plan=wire_plan)

    def ef_step(gs, rs):
        g = jax.tree.map(lambda a: a[0], gs)
        r = jax.tree.map(lambda a: a[0], rs)
        out, st = agg(g, AggregationState(residual=r), ef_specs)
        return out, jax.tree.map(lambda a: a[None], st.residual)

    res_in_specs = {k: P(("pod", "data")) for k in ef_shapes}
    jfn = jax.jit(shard_map(
        ef_step, mesh=mesh,
        in_specs=({k: P(("pod", "data")) for k in ef_shapes}, res_in_specs),
        out_specs=(ef_specs, res_in_specs),
        axis_names={"pod", "data", "model"}, check_vma=False))

    res = {k: jnp.zeros((n_workers,) + sh, jnp.float32)
           for k, sh in ef_shapes.items()}
    outs = []
    for step in range(3):
        per_w = [dyadic_tree(100 + 10 * step + w) for w in range(n_workers)]
        stacked = {k: jnp.asarray(np.stack([pw[k] for pw in per_w]))
                   for k in ef_shapes}
        stacked = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            stacked, {k: P(("pod", "data")) for k in ef_shapes})
        out, res = jfn(stacked, res)
        outs.append((jax.tree.map(np.asarray, out),
                     jax.tree.map(np.asarray, res)))
    return outs


got_ef = run_ef(overlap=False)

# per-leaf reference: the seed architecture, per worker, per leaf
res_ref = {k: np.zeros((n_workers, int(np.prod(sh))), np.float32)
           for k, sh in ef_shapes.items()}
for step in range(3):
    per_w = [dyadic_tree(100 + 10 * step + w) for w in range(n_workers)]
    out_np, res_np = got_ef[step]
    for k, sh in ef_shapes.items():
        n = int(np.prod(sh))
        kk = max(1, int(n * cfg_ef.topk_ratio))
        sparses = []
        for w in range(n_workers):
            flat = jnp.asarray(per_w[w][k].reshape(-1))
            sp, nr = topk_lib.apply_error_feedback(
                flat, jnp.asarray(res_ref[k][w]), kk, exact=True)
            sparses.append(np.asarray(sp))
            res_ref[k][w] = np.asarray(nr)
        want = (np.sum(sparses, axis=0) / n_workers).reshape(sh)
        assert np.array_equal(out_np[k], want), \
            f"EF step {step} leaf {k}: bucketed != per-leaf reference"
        assert np.array_equal(res_np[k].reshape(n_workers, n),
                              res_ref[k]), \
            f"EF step {step} leaf {k}: residuals diverged"
print("OK bucketed topk+EF == per-leaf path bit-for-bit over 3 steps")

got_ef_ov = run_ef(overlap=True)
for step in range(3):
    for k in ef_shapes:
        assert np.array_equal(got_ef[step][0][k], got_ef_ov[step][0][k]), \
            f"overlap schedule diverged at step {step} leaf {k}"
        assert np.array_equal(got_ef[step][1][k], got_ef_ov[step][1][k])
print("OK overlap pipeline == fused bitwise")

# ---- 5. hierarchical OR with a non-power-of-2 inner axis -------------
from repro.core.collectives import (
    _or_allreduce_psum, or_reduce_scatter, or_reduce_scatter_ring)

mesh6 = make_mesh((2, 3), ("pod", "data"), devices=jax.devices()[:6])
W6 = 6
words6 = rng.integers(0, 2**32, size=(W6, 6 * 37), dtype=np.uint32)
expect6 = np.bitwise_or.reduce(words6, axis=0)
put6 = jax.device_put(jnp.asarray(words6.reshape(2, 3, -1)),
                      NamedSharding(mesh6, P("pod", "data", None)))


def _run6(fn, out_specs=P()):
    return np.asarray(jax.jit(shard_map(
        fn, mesh=mesh6, in_specs=P("pod", "data", None),
        out_specs=out_specs, axis_names={"pod", "data"},
        check_vma=False))(put6))

# whatever wire this leg supports (ring/doubling vs psum emulation)
got6 = _run6(lambda a: or_allreduce(a[0, 0], ("pod", "data")))
assert np.array_equal(got6, expect6), "hierarchical non-pow2 or_allreduce"
print("OK or_allreduce hierarchical non-pow2 inner axis")

# the explicit ring(non-pow2 data) -> doubling(pod) composition is
# ppermute-based and full-manual, so it runs on BOTH legs
got6r = _run6(lambda a: or_allreduce_doubling(
    or_allreduce_ring(a[0, 0], "data"), "pod"))
assert np.array_equal(got6r, expect6), "ring+doubling composition"
print("OK ring(non-pow2) + doubling composition")

# chunked psum emulation == unchunked, bit-for-bit
got6c = _run6(lambda a: _or_allreduce_psum(a[0, 0], ("pod", "data"),
                                           chunk_words=16))
got6u = _run6(lambda a: _or_allreduce_psum(a[0, 0], ("pod", "data"),
                                           chunk_words=1 << 30))
assert np.array_equal(got6c, expect6) and np.array_equal(got6u, expect6)
print("OK chunked == unchunked psum OR emulation")

# ---- 6. or_reduce_scatter ------------------------------------------
# Multi-axis on the (2,2,2) mesh: rank-major chunks must reassemble to
# the full numpy OR via the same out_specs tiling psum_scatter uses.
wordsRS = rng.integers(0, 2**32, size=(W, 4 * 41), dtype=np.uint32)
expectRS = np.bitwise_or.reduce(wordsRS, axis=0)
putRS = jax.device_put(jnp.asarray(wordsRS.reshape(2, 2, -1)),
                       NamedSharding(mesh, P("pod", "data", None)))
gotRS = np.asarray(jax.jit(shard_map(
    lambda a: or_reduce_scatter(
        a[0, 0], ("pod", "data"),
        axis_indices={ax: jax.lax.axis_index(ax) for ax in ("pod", "data")}),
    mesh=mesh, in_specs=P("pod", "data", None),
    out_specs=P(("pod", "data")), axis_names={"pod", "data", "model"},
    check_vma=False))(putRS))
assert np.array_equal(gotRS, expectRS), "or_reduce_scatter multi-axis"
print("OK or_reduce_scatter multi-axis rank-major")

# chunk placement must match psum_scatter's exactly
gotPS = np.asarray(jax.jit(shard_map(
    lambda a: jax.lax.psum_scatter(a[0, 0].astype(jnp.float64
                                                  if jax.config.jax_enable_x64
                                                  else jnp.float32),
                                   ("pod", "data"), scatter_dimension=0,
                                   tiled=True),
    mesh=mesh, in_specs=P("pod", "data", None),
    out_specs=P(("pod", "data")), axis_names={"pod", "data", "model"},
    check_vma=False))(jax.device_put(
        jnp.asarray((wordsRS & 0xFFFF).astype(np.float32).reshape(2, 2, -1)),
        NamedSharding(mesh, P("pod", "data", None)))))
assert np.array_equal(gotPS, (wordsRS & 0xFFFF).astype(np.float32).sum(0)), \
    "psum_scatter chunk order diverged from or_reduce_scatter's"
print("OK psum_scatter chunk order == or_reduce_scatter")

# single non-pow2 axis ring (data=3 on the 6-device mesh)
words3 = rng.integers(0, 2**32, size=(3, 3 * 29), dtype=np.uint32)
got3 = np.asarray(jax.jit(shard_map(
    lambda a: or_reduce_scatter_ring(a[0], "data"),
    mesh=mesh6, in_specs=P("data", None), out_specs=P("data"),
    axis_names={"pod", "data"}, check_vma=False))(
        jax.device_put(jnp.asarray(words3),
                       NamedSharding(mesh6, P("data", None)))))
assert np.array_equal(got3, np.bitwise_or.reduce(words3, 0)), \
    "or_reduce_scatter_ring non-pow2"
print("OK or_reduce_scatter_ring non-pow2 axis")

# ---- 7. native RS wire == emulated == CompressedAggregator (3 EF steps)
got_rs_native = run_ef(overlap=False, name="compressed_rs",
                       rs_wire="native")
got_rs_emul = run_ef(overlap=False, name="compressed_rs",
                     rs_wire="emulate")
for step in range(3):
    for k in ef_shapes:
        assert np.array_equal(got_ef[step][0][k], got_rs_native[step][0][k]), \
            f"native RS diverged from compressed at step {step} leaf {k}"
        assert np.array_equal(got_ef[step][1][k], got_rs_native[step][1][k]), \
            f"native RS residuals diverged at step {step} leaf {k}"
        assert np.array_equal(got_rs_native[step][0][k],
                              got_rs_emul[step][0][k]), \
            f"native RS != emulated RS at step {step} leaf {k}"
        assert np.array_equal(got_rs_native[step][1][k],
                              got_rs_emul[step][1][k])
print("OK native RS wire == emulated RS == CompressedAggregator, 3 EF steps")

# ---- 8. in-network tier: tree collectives + compressed_innet ---------
from repro.core.bucketing import make_bucket_plan
from repro.core.compressor import HomomorphicCompressor, CompressedLeaf
from repro.net import FixedPointWire, make_topology, tree_all_reduce

# tree_all_reduce == psum / numpy OR, both topology kinds, (2,2)-axes
ints8 = rng.integers(-2**20, 2**20, size=(W, 257), dtype=np.int32)
wordsT = rng.integers(0, 2**32, size=(W, 123), dtype=np.uint32)
for kind in ("flat", "tor_spine"):
    topoK = make_topology(kind, mesh, ("pod", "data"))

    def tree_fn(a, w, topoK=topoK, use_ppermute=True):
        idx = {ax: jax.lax.axis_index(ax) for ax in ("pod", "data")}
        return (tree_all_reduce(a[0, 0], topoK, "add", axis_indices=idx,
                                use_ppermute=use_ppermute),
                tree_all_reduce(w[0, 0], topoK, "or", axis_indices=idx,
                                use_ppermute=use_ppermute))

    for use_pp in (True, False):   # ppermute tree vs psum/OR fallback
        gi, gw = jax.jit(shard_map(
            lambda a, w, t=topoK, u=use_pp: tree_fn(a, w, t, u),
            mesh=mesh,
            in_specs=(P("pod", "data", None), P("pod", "data", None)),
            out_specs=(P(), P()), axis_names={"pod", "data", "model"},
            check_vma=False))(
            jax.device_put(jnp.asarray(ints8.reshape(2, 2, -1)),
                           NamedSharding(mesh, P("pod", "data", None))),
            jax.device_put(jnp.asarray(wordsT.reshape(2, 2, -1)),
                           NamedSharding(mesh, P("pod", "data", None))))
        assert np.array_equal(np.asarray(gi), ints8.sum(0)), (kind, use_pp)
        assert np.array_equal(np.asarray(gw),
                              np.bitwise_or.reduce(wordsT, 0)), (kind, use_pp)
    print(f"OK tree_all_reduce == psum/OR ({kind}, tree + fallback)")

# non-pow2 inner axis on the 6-device mesh
ints6 = rng.integers(-2**20, 2**20, size=(6, 37), dtype=np.int32)
topo6 = make_topology("tor_spine", mesh6, ("pod", "data"))
g6 = jax.jit(shard_map(
    lambda a: tree_all_reduce(a[0, 0], topo6, "add", use_ppermute=True),
    mesh=mesh6, in_specs=P("pod", "data", None), out_specs=P(),
    axis_names={"pod", "data"}, check_vma=False))(
    jax.device_put(jnp.asarray(ints6.reshape(2, 3, -1)),
                   NamedSharding(mesh6, P("pod", "data", None))))
assert np.array_equal(np.asarray(g6), ints6.sum(0)), "tree non-pow2"
print("OK tree_all_reduce non-pow2 inner axis")

# compressed_innet, f32 wire: bit-identical to CompressedAggregator
got_in = run_ef(overlap=False, name="compressed_innet")
for step in range(3):
    for k in ef_shapes:
        assert np.array_equal(got_ef[step][0][k], got_in[step][0][k]), \
            f"innet f32 diverged from compressed at step {step} leaf {k}"
        assert np.array_equal(got_ef[step][1][k], got_in[step][1][k]), \
            f"innet f32 residuals diverged at step {step} leaf {k}"
print("OK compressed_innet f32 == CompressedAggregator, 3 EF steps")

# fxp32 wire: the dyadic values (sign * 2^e, |e| <= 2) sit far inside
# the fixed-point mantissa budget, so the documented quantize -> integer
# sum -> dequantize roundtrip is *exact* here and the fxp32 output must
# equal the f32 path bit-for-bit — for both topology kinds.
got_fx = run_ef(overlap=False, name="compressed_innet",
                wire_dtype="fxp32")
got_fx_ts = run_ef(overlap=False, name="compressed_innet",
                   wire_dtype="fxp32", topology="tor_spine")
for step in range(3):
    for k in ef_shapes:
        assert np.array_equal(got_ef[step][0][k], got_fx[step][0][k]), \
            f"innet fxp32 diverged at step {step} leaf {k}"
        assert np.array_equal(got_fx[step][0][k], got_fx_ts[step][0][k]), \
            f"tor_spine diverged from flat at step {step} leaf {k}"
        assert np.array_equal(got_ef[step][1][k], got_fx[step][1][k])
        assert np.array_equal(got_fx[step][1][k], got_fx_ts[step][1][k])
print("OK innet fxp32 (flat & tor_spine) == f32 on dyadic data, 3 EF steps")

# Independent host replay of the documented codec roundtrip: per-worker
# sparsify (the same per-leaf EF reference as section 3) -> pack ->
# compress -> shared-exponent quantize -> int32 sum -> dequantize -> OR
# bitmaps -> peel -> unpack/W. Must match the in-mesh fxp32 wire
# bit-for-bit at every step.
cfg_fx = dataclasses.replace(cfg_ef, wire_dtype="fxp32")
comp_fx = HomomorphicCompressor(cfg_fx)
plan_fx = make_bucket_plan(
    {k: np.zeros(sh, np.float32) for k, sh in ef_shapes.items()}, cfg_fx)
wire_fx = FixedPointWire(workers=n_workers)
res_fx = {k: np.zeros((n_workers, int(np.prod(sh))), np.float32)
          for k, sh in ef_shapes.items()}
fx_replay_refs = []   # per-step replay trees, reused by section 13
for step in range(3):
    per_w = [dyadic_tree(100 + 10 * step + w) for w in range(n_workers)]
    sks, wrds = [], []
    for w in range(n_workers):
        sp_tree = {}
        for k, sh in ef_shapes.items():
            n = int(np.prod(sh))
            kk = max(1, int(n * cfg_fx.topk_ratio))
            sp, nr = topk_lib.apply_error_feedback(
                jnp.asarray(per_w[w][k].reshape(-1)),
                jnp.asarray(res_fx[k][w]), kk, exact=True)
            sp_tree[k] = np.asarray(sp).reshape(sh)
            res_fx[k][w] = np.asarray(nr)
        c = comp_fx.compress(plan_fx.pack(
            jax.tree.map(jnp.asarray, sp_tree)).reshape(-1))
        sks.append(np.asarray(c.sketch))
        wrds.append(np.asarray(c.index_words))
    dec = wire_fx.roundtrip_reference(
        [s.reshape(plan_fx.n_buckets, -1) for s in sks])
    w_or = wrds[0]
    for wd in wrds[1:]:
        w_or = w_or | wd
    rec = comp_fx.recover(
        CompressedLeaf(sketch=jnp.asarray(dec).reshape(sks[0].shape),
                       index_words=jnp.asarray(w_or)), plan_fx.padded)
    ref_tree = plan_fx.unpack(
        jnp.asarray(rec).reshape(plan_fx.n_buckets, plan_fx.bucket_elems)
        / n_workers)
    fx_replay_refs.append(jax.tree.map(np.asarray, ref_tree))
    out_fx = got_fx[step][0]
    for k in ef_shapes:
        assert np.array_equal(out_fx[k], np.asarray(ref_tree[k])), \
            f"fxp32 wire != documented codec roundtrip, step {step} leaf {k}"
print("OK innet fxp32 == host replay of the documented codec roundtrip")

# ---- 9. stream scheduler (PR 5): chunked == unchunked, all strategies
# The 5-bucket EF stream over W=4 ranks: per-rank bucket count is
# ceil(5/4) = 2, so the native RS wire admits chunk grids {1, 2};
# stream_chunks=3 on the AllReduce wire is non-divisible (pads to 6);
# switch_slots=2 gives the innet tree 3 windows. Every grid must be
# bit-invisible over 3 EF steps.
stream_arms = [
    ("compressed overlap=per-bucket", dict(overlap=True)),
    ("compressed chunks=3 (non-divisible)",
     dict(overlap=False, stream_chunks=3)),
    ("compressed_rs native overlap=per-rank-chunk",
     dict(overlap=True, name="compressed_rs", rs_wire="native")),
    ("compressed_rs native chunks=2",
     dict(overlap=False, name="compressed_rs", rs_wire="native",
          stream_chunks=2)),
    ("compressed_rs emulated chunks=3",
     dict(overlap=False, name="compressed_rs", rs_wire="emulate",
          stream_chunks=3)),
    ("compressed_innet f32 windows=2",
     dict(overlap=True, name="compressed_innet", switch_slots=2)),
    ("compressed_innet fxp32 windows=2",
     dict(overlap=True, name="compressed_innet", wire_dtype="fxp32",
          switch_slots=2)),
]
for label, kw in stream_arms:
    got_s = run_ef(**kw)
    for step in range(3):
        for k in ef_shapes:
            assert np.array_equal(got_ef[step][0][k], got_s[step][0][k]), \
                f"[{label}] diverged at step {step} leaf {k}"
            assert np.array_equal(got_ef[step][1][k], got_s[step][1][k]), \
                f"[{label}] residuals diverged at step {step} leaf {k}"
    print(f"OK stream scheduler: {label} == fused, 3 EF steps")

# dense ignores the chunk knob entirely (no wire chunks to cut)
got_d1 = run_ef(overlap=False, name="dense")
got_d2 = run_ef(overlap=False, name="dense", stream_chunks=3)
for step in range(3):
    for k in ef_shapes:
        assert np.array_equal(got_d1[step][0][k], got_d2[step][0][k])
print("OK stream scheduler: dense chunked == unchunked")

# forcing a grid that splits a per-rank RS boundary names the constraint
try:
    run_ef(overlap=False, name="compressed_rs", rs_wire="native",
           stream_chunks=3)
except ValueError as e:
    assert "ceil(n_buckets/W)" in str(e), e
else:
    raise AssertionError("boundary-splitting stream_chunks did not raise")
print("OK stream scheduler: RS boundary split raises ValueError")

# windowed tree mode == one-shot tree == psum/OR (both combiners)
topoW = make_topology("flat", mesh, ("pod", "data"))
giW, gwW = jax.jit(shard_map(
    lambda a, w: (
        tree_all_reduce(a[0, 0], topoW, "add",
                        axis_indices={ax: jax.lax.axis_index(ax)
                                      for ax in ("pod", "data")},
                        use_ppermute=True, window_slots=3),
        tree_all_reduce(w[0, 0], topoW, "or",
                        axis_indices={ax: jax.lax.axis_index(ax)
                                      for ax in ("pod", "data")},
                        use_ppermute=True, window_slots=3)),
    mesh=mesh,
    in_specs=(P("pod", "data", None), P("pod", "data", None)),
    out_specs=(P(), P()), axis_names={"pod", "data", "model"},
    check_vma=False))(
    jax.device_put(jnp.asarray(ints8.reshape(2, 2, -1)),
                   NamedSharding(mesh, P("pod", "data", None))),
    jax.device_put(jnp.asarray(wordsT.reshape(2, 2, -1)),
                   NamedSharding(mesh, P("pod", "data", None))))
assert np.array_equal(np.asarray(giW), ints8.sum(0))
assert np.array_equal(np.asarray(gwW), np.bitwise_or.reduce(wordsT, 0))
print("OK tree_all_reduce windowed mode == one-shot")

# ---- 10. ZeRO-1 gather-skip: aligned chunk grid feeds optimizer shards
# Two 4-bucket leaves (8-bucket stream), W=4: with stream_chunks=2 the
# grid is 2 chunks x 4 buckets, rank r owns bucket r of each chunk —
# exactly each leaf's dim-0 ZeRO-1 slice r. The aggregator must skip
# the recovered-chunk all_gather, return leaves exact inside this
# rank's slice (zero outside), and keep residuals bit-identical.
E_skip = 1536  # cfg_ef bucket_elems (2 blocks)
skip_shapes = {"wa": (4 * E_skip,), "wb": (4 * E_skip,)}
skip_specs = {k: P() for k in skip_shapes}


def skip_tree(seed):
    r = np.random.default_rng(seed)
    out = {}
    for k, sh in skip_shapes.items():
        n = int(np.prod(sh))
        g = np.zeros(n, np.float32)
        nz = max(1, int(n * 0.2))
        idx = r.choice(n, size=nz, replace=False)
        g[idx] = (r.choice([-1.0, 1.0], size=nz)
                  * np.exp2(r.integers(-2, 3, size=nz))).astype(np.float32)
        out[k] = g.reshape(sh)
    return out


def run_skip(name, zero1_dims=None, **overrides):
    cfg = dataclasses.replace(cfg_ef, **overrides)
    agg = make_aggregator(name, cfg, mesh, ("pod", "data"), (),
                          outer_manual=("pod", "data", "model"),
                          zero1_dims=zero1_dims)

    def ef_step(gs, rs):
        g = jax.tree.map(lambda a: a[0], gs)
        r = jax.tree.map(lambda a: a[0], rs)
        out, st = agg(g, AggregationState(residual=r), skip_specs)
        # keep per-rank outputs visible (the skip path returns
        # rank-local data): stack on the dp axes
        return (jax.tree.map(lambda a: a[None], out),
                jax.tree.map(lambda a: a[None], st.residual))

    ris = {k: P(("pod", "data")) for k in skip_shapes}
    jfn = jax.jit(shard_map(
        ef_step, mesh=mesh, in_specs=(ris, ris), out_specs=(ris, ris),
        axis_names={"pod", "data", "model"}, check_vma=False))
    res = {k: jnp.zeros((n_workers,) + sh, jnp.float32)
           for k, sh in skip_shapes.items()}
    outs = []
    for step in range(3):
        per_w = [skip_tree(500 + 10 * step + w) for w in range(n_workers)]
        stacked = {k: jnp.asarray(np.stack([pw[k] for pw in per_w]))
                   for k in skip_shapes}
        stacked = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            stacked, ris)
        out, res = jfn(stacked, res)
        outs.append((jax.tree.map(np.asarray, out),
                     jax.tree.map(np.asarray, res)))
    return agg, jfn, outs


agg_skip, jfn_skip, got_skip = run_skip(
    "compressed_rs", zero1_dims=(0, 0), rs_wire="native", stream_chunks=2)
assert agg_skip.gather_skip_active(
    {k: np.zeros(sh, np.float32) for k, sh in skip_shapes.items()}), \
    "aligned grid did not activate the gather skip"
# misaligned (fused) grid and missing zero1_dims keep the gather
agg_1c, _, _ = run_skip("compressed_rs", zero1_dims=(0, 0),
                        rs_wire="native", stream_chunks=1)
assert not agg_1c.gather_skip_active(
    {k: np.zeros(sh, np.float32) for k, sh in skip_shapes.items()})
_, _, got_full = run_skip("compressed", rs_wire="auto")
for step in range(3):
    for k in skip_shapes:
        # residuals are per-leaf, before the wire: identical
        assert np.array_equal(got_skip[step][1][k], got_full[step][1][k]), \
            f"gather-skip residuals diverged at step {step} leaf {k}"
        for r in range(n_workers):
            sl = slice(r * E_skip, (r + 1) * E_skip)
            assert np.array_equal(got_skip[step][0][k][r][sl],
                                  got_full[step][0][k][r][sl]), \
                f"gather-skip slice wrong at step {step} leaf {k} rank {r}"
            mask = np.ones(4 * E_skip, bool)
            mask[sl] = False
            assert not got_skip[step][0][k][r][mask].any(), \
                f"gather-skip off-slice values leaked at step {step} " \
                f"leaf {k} rank {r}"
print("OK gather-skip: per-rank slices exact, off-slice zero, 3 EF steps")

# the skip path must launch NO all_gather; the gathered path must
agg_g, jfn_g, _ = run_skip("compressed_rs", rs_wire="native",
                           stream_chunks=2)
_stk = {k: jax.device_put(
    jnp.zeros((n_workers,) + sh, jnp.float32),
    NamedSharding(mesh, P(("pod", "data"))))
    for k, sh in skip_shapes.items()}
_res = {k: jnp.zeros((n_workers,) + sh, jnp.float32)
        for k, sh in skip_shapes.items()}
assert "all_gather" not in str(jax.make_jaxpr(jfn_skip)(_stk, _res)), \
    "gather-skip path still launches all_gather"
assert "all_gather" in str(jax.make_jaxpr(jfn_g)(_stk, _res)), \
    "gathered path lost its all_gather"
print("OK gather-skip: no all_gather in the skip jaxpr")

# ---- 11. mixed per-bucket wire plans (PR 6) --------------------------
# The EF stream packs into 5 buckets; carve it into groups spanning all
# four wires. Per the numerics contract every group encodes at its
# global block offsets, so any plan must reproduce the fixed
# ``compressed`` run bit-for-bit — through the ``compressed`` executor
# (explicit plan) and the ``auto`` strategy alike.
from repro.core.wireplan import WireGroup, WirePlan

_nb_ef = make_bucket_plan(
    {k: np.zeros(sh, np.float32) for k, sh in ef_shapes.items()},
    cfg_ef).n_buckets
assert _nb_ef == 5, _nb_ef
mixed_plans = [
    ("dense[0:2] | compressed[2:4] | rs[4:5]",
     WirePlan(5, (WireGroup(0, 2, "dense"),
                  WireGroup(2, 2, "compressed"),
                  WireGroup(4, 1, "compressed_rs")))),
    ("innet[0:3] | dense[3:5]",
     WirePlan(5, (WireGroup(0, 3, "compressed_innet"),
                  WireGroup(3, 2, "dense")))),
]
for label, wp in mixed_plans:
    for strat in ("compressed", "auto"):
        got_mx = run_ef(overlap=False, name=strat, wire_plan=wp)
        for step in range(3):
            for k in ef_shapes:
                assert np.array_equal(got_ef[step][0][k],
                                      got_mx[step][0][k]), \
                    f"[{strat}: {label}] diverged at step {step} leaf {k}"
                assert np.array_equal(got_ef[step][1][k],
                                      got_mx[step][1][k]), \
                    f"[{strat}: {label}] residuals diverged at step " \
                    f"{step} leaf {k}"
        print(f"OK mixed wire plan ({strat}): {label} == compressed, "
              "3 EF steps")

# ---- 12. the all-to-all exchange (PR 8) ------------------------------
# The expert-parallel dispatch/combine wire: each rank holds a stacked
# (W, n) payload — slice r routed to rank r — and the exchange must
# deliver merged_r = sum_s payload_s[r] at rank r. Dyadic payloads make
# every fp sum exact, so the compressed permute wire (sketch add +
# bitmap OR in flight, ratio 2.5 = always-exact peel) must equal the
# dense wire AND the numpy reference bit-for-bit, over 3 steps of
# evolving payloads, on the native single-axis ppermute leg (W=2 over
# "data"; the region is full-manual), the
# psum-emulated multi-axis leg (W=4 over pod x data), and a chunked
# (stream_chunks=2) lane grid.
from repro.core.aggregators import make_exchange

cfg_a2a = dataclasses.replace(cfg_ef, ratio=2.5, topk_ratio=None,
                              error_feedback=False)
N_DEST = 2 * 1536          # 2 buckets/dest: the chunked grid divides it


def dyadic_payload(seed, w):
    r = np.random.default_rng(seed)
    out = np.zeros((w, N_DEST), np.float32)
    for d in range(w):
        n_nz = int(N_DEST * 0.9)
        idx = r.choice(N_DEST, size=n_nz, replace=False)
        out[d, idx] = (r.choice([-1.0, 1.0], size=n_nz)
                       * np.exp2(r.integers(-2, 3, size=n_nz)))
    return out


for label, ep_axes, w_ep, in_spec, out_spec in (
        ("native W=2 (data)", ("data",), 2,
         P("data", None, None), P("data", None)),
        ("emulated W=4 (pod,data)", ("pod", "data"), 4,
         P("pod", "data", None, None), P("pod", "data", None))):
    for chunks in (None, 2):
        outs = {}
        for wire in ("dense", "compressed"):
            cfg_w = dataclasses.replace(cfg_a2a, stream_chunks=chunks)
            ex = make_exchange(wire, cfg_w, mesh, ep_axes)

            def body(stack, ex=ex, n_lead=len(ep_axes)):
                local = stack
                for _ in range(n_lead):
                    local = local[0]
                merged = ex({"g": local})["g"]
                for _ in range(n_lead):
                    merged = merged[None]
                return merged

            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
                axis_names={"pod", "data", "model"}, check_vma=False))
            step_outs = []
            for step in range(3):
                pay = np.stack([dyadic_payload(1000 + 10 * step + s, w_ep)
                                for s in range(w_ep)])
                lead = (2, 2) if len(ep_axes) > 1 else (2,)
                put_a2a = jax.device_put(
                    jnp.asarray(pay.reshape(lead + (w_ep, N_DEST))),
                    NamedSharding(mesh, in_spec))
                got = np.asarray(fn(put_a2a)).reshape(w_ep, N_DEST)
                want = pay.sum(axis=0)     # merged_r = sum_s payload_s[r]
                assert np.array_equal(got, want), \
                    (label, chunks, wire, step)
                step_outs.append(got)
            outs[wire] = step_outs
        for step in range(3):
            assert np.array_equal(outs["dense"][step],
                                  outs["compressed"][step]), \
                (label, chunks, step)
        grid = f"chunked x{chunks}" if chunks else "fused"
        print(f"OK a2a exchange [{label}, {grid}]: compressed == dense "
              "== numpy, 3 steps")

# ---- 4. reduce-scatter aggregator on the TP-sharded tree -------------
got_rs = jax.jit(shard_map(
    lambda gs: compressed_all_reduce(
        jax.tree.map(lambda a: a[0, 0], gs),
        init_aggregation_state(jax.tree.map(lambda a: a[0, 0], gs), cfg),
        specs, mesh, cfg, dp_axes=("pod", "data"), tp_axes=("model",),
        reduce_scatter=True)[0],
    mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
    axis_names={"pod", "data"}, check_vma=False))(put)
for k in ("w1", "w2", "scale"):
    ok = np.allclose(np.asarray(got_rs[k]), mean_ref[k], atol=1e-5)
    print(f"{'OK' if ok else 'FAIL'} compressed_rs[{k}] "
          f"maxerr={np.abs(np.asarray(got_rs[k]) - mean_ref[k]).max():.2e}")
    assert ok, k

# ---- 13. elastic aggregation service (PR 9/10) vs the in-mesh strategies
# Fold-equivalence gate: a fixed-membership elastic round is the same
# aggregate as the synchronous collective. Per EF step, every client
# contributes the same dyadic gradient its in-mesh worker saw and the
# server folds payloads in a permuted arrival order; the finalized
# stream must match the `compressed` strategy's psum+OR output (f32)
# and both the `compressed_innet` output and section 8's host replay of
# FixedPointWire.roundtrip_reference (fxp32) — bit-for-bit, residuals
# included. The PR 10 arm replays the same schedule through the
# sharded+batched fold pipeline (2 shard engines, microbatches of 3):
# f32 matches via the canonical client-sorted reduction order, fxp32 in
# any arrival order — the scale-out path changes nothing the wire can
# observe.
from repro.elastic import ElasticClient, ElasticServer

el_template = {k: np.zeros(sh, np.float32) for k, sh in ef_shapes.items()}
perm_rng = np.random.default_rng(13)
for wire_name, el_cfg, refs in (
        ("f32", cfg_ef, [(got_ef[s][0], got_ef[s][1]) for s in range(3)]),
        ("fxp32", cfg_fx, [(got_fx[s][0], got_fx[s][1]) for s in range(3)])):
    for arm, srv_kwargs in (("sequential", {}),
                            ("sharded S=2 b=3",
                             {"n_shards": 2, "batch_size": 3})):
        srv = ElasticServer(el_template, el_cfg, **srv_kwargs)
        clients = [ElasticClient(w, el_cfg) for w in range(n_workers)]
        for w in range(n_workers):
            srv.join(w)
        for step in range(3):
            contract = srv.open_round()
            trees = [jax.tree.map(jnp.asarray,
                                  dyadic_tree(100 + 10 * step + w))
                     for w in range(n_workers)]
            if wire_name == "fxp32":
                for w in range(n_workers):
                    srv.submit_exponents(
                        clients[w].propose(contract, trees[w]))
                shared = srv.seal_exponents()
                payloads = [clients[w].payload(contract, shared)
                            for w in range(n_workers)]
            else:
                payloads = [clients[w].contribute(contract, trees[w])
                            for w in range(n_workers)]
            for w in perm_rng.permutation(n_workers):
                assert srv.submit(payloads[w]) == "folded"
            stream, rep = srv.close_round()
            assert rep.close_reason == "complete" and \
                rep.folded == n_workers
            out_tree = jax.tree.map(np.asarray,
                                    srv.plan.unpack(stream / n_workers))
            want_out, want_res = refs[step]
            for k in ef_shapes:
                assert np.array_equal(out_tree[k], want_out[k]), \
                    (f"elastic {wire_name} [{arm}] != in-mesh, "
                     f"step {step} leaf {k}")
                if wire_name == "fxp32":
                    assert np.array_equal(out_tree[k],
                                          fx_replay_refs[step][k]), \
                        (f"elastic fxp32 [{arm}] != codec replay, "
                         f"step {step} leaf {k}")
                for w in range(n_workers):
                    assert np.array_equal(
                        np.asarray(clients[w].residual[k]),
                        want_res[k][w]), \
                        (f"elastic {wire_name} [{arm}] EF residual "
                         f"drift, step {step} leaf {k} client {w}")
        print(f"OK elastic {wire_name} [{arm}] rounds == in-mesh "
              "aggregate, 3 EF steps")

print("ALL OK")
