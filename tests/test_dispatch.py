"""Backend-dispatch parity: the full compress -> aggregate -> recover
roundtrip must be bit-for-bit identical between ``use_pallas="always"``
(Pallas kernels, interpret mode on CPU) and ``"never"`` (jnp reference) —
both at the compressor level and through the bucketed aggregator layer
(fused and overlap-pipelined; plain, reduce-scatter — over both its
native psum_scatter/OR-RS wire and the psum+slice emulation — and the
in-network tree, over both its f32 and fixed-point wires).

Test values are dyadic (sign * 2^e, small e) so every floating-point sum
along either backend's reduction order is exact — bitwise equality then
checks the *math*, not addition-order luck.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.core import CompressionConfig, HomomorphicCompressor, CompressedLeaf
from repro.core import topk as topk_lib
from repro.core.aggregators import make_aggregator
from repro.core.collectives import AggregationState, init_aggregation_state


def dyadic_sparse(n, frac, seed):
    r = np.random.default_rng(seed)
    x = np.zeros(n, np.float32)
    k = int(n * frac)
    idx = r.choice(n, size=k, replace=False)
    x[idx] = (r.choice([-1.0, 1.0], size=k)
              * np.exp2(r.integers(-2, 3, size=k))).astype(np.float32)
    return x


# lanes=128 keeps interpret-mode Pallas fast; chunk_blocks=4 and
# encode_block_tile=3 force the lax.map chunking and the multi-block
# grid-cell tiling (with padding) on the 11-block leaf below.
BASE = CompressionConfig(ratio=0.3, lanes=128, rows=6, rounds=10,
                         chunk_blocks=4, encode_block_tile=3)


def _roundtrip(cfg, n, workers=2):
    comp = HomomorphicCompressor(cfg)
    xs = [dyadic_sparse(n, 0.05, seed=s) for s in range(workers)]
    cs = [comp.compress(jnp.asarray(x)) for x in xs]
    agg = cs[0]
    for c in cs[1:]:
        agg = CompressedLeaf(sketch=agg.sketch + c.sketch,
                             index_words=agg.index_words | c.index_words)
    out, stats = comp.recover(agg, n, with_stats=True)
    return [np.asarray(c.sketch) for c in cs], np.asarray(out), stats, \
        np.sum(xs, axis=0)


@pytest.mark.parametrize("nb", [1, 11], ids=["single-chunk", "chunked"])
def test_roundtrip_parity_bitwise(nb):
    n = nb * BASE.block_elems - (BASE.lanes // 2 if nb > 1 else 0)
    never = dataclasses.replace(BASE, use_pallas="never")
    always = dataclasses.replace(BASE, use_pallas="always")
    sk_n, out_n, st_n, want = _roundtrip(never, n)
    sk_a, out_a, st_a, _ = _roundtrip(always, n)
    for a, b in zip(sk_n, sk_a):
        assert np.array_equal(a, b), "per-worker sketches differ"
    assert np.array_equal(out_n, out_a), "recovered gradients differ"
    assert int(st_n.residual) == 0 and int(st_a.residual) == 0
    assert int(st_n.peeled) == int(st_a.peeled)
    # lossless regime + dyadic values: recovery is exact, not approximate
    assert np.array_equal(out_n, want)


def test_estimate_runs_on_both_backends():
    n = 3 * BASE.block_elems
    x = dyadic_sparse(n, 0.02, seed=7)
    outs = []
    for policy in ("never", "always"):
        cfg = dataclasses.replace(BASE, use_pallas=policy)
        comp = HomomorphicCompressor(cfg)
        outs.append(np.asarray(comp.estimate(comp.compress(jnp.asarray(x)), n)))
    assert np.array_equal(outs[0], outs[1])


# ----------------------------------------------------------------------
# Bucketed aggregator roundtrip (PR 2): pack -> sparsify/EF -> encode ->
# psum/OR -> peel -> unpack, through both strategies and both backends.
# Runs inside a real (1-device) shard_map so the collectives are genuine.
# ----------------------------------------------------------------------

# ratio=1.0 keeps peel capacity (~81%) far above the post-top-k density
# even with dyadic tie overshoot; topk_ratio < nonzero fraction so the
# sparsifier really cuts and residuals are nonzero. bucket_bytes =
# 2 blocks -> the 4-leaf tree below spans several buckets, with one leaf
# larger than a bucket and one mixed-dtype leaf.
_AGG0 = dataclasses.replace(BASE, ratio=1.0, topk_ratio=0.1,
                            topk_exact=True, error_feedback=True)
AGG_BASE = dataclasses.replace(_AGG0, bucket_bytes=2 * _AGG0.block_elems * 4)


def _agg_tree(seed=0):
    r = np.random.default_rng(seed)

    def dyadic(n, frac, dtype=np.float32):
        return dyadic_sparse(n, frac, seed=r.integers(1 << 30)).astype(dtype)

    return {
        "big": dyadic(3 * AGG_BASE.block_elems * 2 + 101, 0.3),
        "mat": dyadic(40 * 64, 0.3).reshape(40, 64),
        "half": dyadic(900, 0.3, np.float16),
        "tiny": dyadic(9, 0.5),
    }


def _run_aggregator(cfg, name, steps=1, wire_plan=None):
    mesh = make_mesh((1,), ("data",))
    tree = jax.tree.map(jnp.asarray, _agg_tree())
    specs = jax.tree.map(lambda _: P(), tree)
    agg = make_aggregator(name, cfg, mesh, ("data",), ("model",),
                          outer_manual=("data",), wire_plan=wire_plan)

    def fn(g, r):
        out, st = agg(g, AggregationState(residual=r), specs)
        return out, st.residual

    jfn = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(specs, specs), out_specs=(specs, specs),
        axis_names={"data"}, check_vma=False))
    res = init_aggregation_state(tree, cfg).residual
    outs = []
    for s in range(steps):
        g = jax.tree.map(jnp.asarray, _agg_tree(seed=s))
        out, res = jfn(g, res)
        outs.append(jax.tree.map(np.asarray, out))
    return outs, jax.tree.map(np.asarray, res)


@pytest.mark.parametrize("name", ["compressed", "compressed_rs",
                                  "compressed_innet"])
@pytest.mark.parametrize("overlap", [False, True], ids=["fused", "overlap"])
def test_bucketed_aggregate_backend_parity(name, overlap):
    cfg_n = dataclasses.replace(AGG_BASE, use_pallas="never", overlap=overlap)
    cfg_a = dataclasses.replace(AGG_BASE, use_pallas="always", overlap=overlap)
    (out_n,), res_n = _run_aggregator(cfg_n, name)
    (out_a,), res_a = _run_aggregator(cfg_a, name)
    for k in out_n:
        assert np.array_equal(out_n[k], out_a[k]), f"grads differ: {k}"
        assert out_n[k].dtype == out_a[k].dtype
        assert np.array_equal(res_n[k], res_a[k]), f"residuals differ: {k}"
    # single worker + dyadic values: the roundtrip is exact, so the
    # aggregate must equal the sparsified (g + residual) per leaf
    tree = _agg_tree()
    for k, g in tree.items():
        flat = jnp.asarray(g.reshape(-1), jnp.float32)
        k_budget = max(1, int(flat.shape[0] * AGG_BASE.topk_ratio))
        want, want_res = topk_lib.apply_error_feedback(
            flat, jnp.zeros_like(flat), k_budget, exact=True)
        np.testing.assert_array_equal(
            out_n[k].reshape(-1), np.asarray(want).astype(g.dtype), err_msg=k)
        np.testing.assert_array_equal(res_n[k].reshape(-1),
                                      np.asarray(want_res), err_msg=k)


def test_bucketed_overlap_matches_fused_bitwise():
    for name in ("compressed", "compressed_rs"):
        (fused,), rf = _run_aggregator(
            dataclasses.replace(AGG_BASE, use_pallas="never"), name)
        (over,), ro = _run_aggregator(
            dataclasses.replace(AGG_BASE, use_pallas="never", overlap=True),
            name)
        for k in fused:
            assert np.array_equal(fused[k], over[k]), (name, k)
            assert np.array_equal(rf[k], ro[k]), (name, k)


# The shared stream scheduler (PR 5): an explicit wire-chunk grid must be
# bit-invisible for EVERY strategy on EVERY backend over 3 error-feedback
# steps. stream_chunks=4 over the 6-bucket test stream is non-divisible
# (zero-pads to 8); switch_slots=1 gives the innet tree 6 one-bucket
# windows so any chunk count spans whole windows. ``dense`` has no wire
# chunks — it must simply ignore the knob.
@pytest.mark.parametrize("name", ["dense", "compressed", "compressed_rs",
                                  "compressed_innet"])
@pytest.mark.parametrize("backend", ["never", "always"])
def test_stream_chunked_matches_unchunked_bitwise(name, backend):
    base = dataclasses.replace(AGG_BASE, use_pallas=backend)
    chunked = dataclasses.replace(base, stream_chunks=4, switch_slots=1)
    outs_f, res_f = _run_aggregator(base, name, steps=3)
    outs_c, res_c = _run_aggregator(chunked, name, steps=3)
    for step, (of, oc) in enumerate(zip(outs_f, outs_c)):
        for k in of:
            assert np.array_equal(of[k], oc[k]), (name, step, k)
    for k in res_f:
        assert np.array_equal(res_f[k], res_c[k]), (name, k)


def test_rs_matches_plain_bitwise():
    (plain,), _ = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas="never"), "compressed")
    (rs,), _ = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas="never"), "compressed_rs")
    for k in plain:
        assert np.array_equal(plain[k], rs[k]), k


# The innet f32 wire reuses the AllReduce collectives (bit-parity by
# construction); the fxp32 wire quantizes through the fixed-point codec,
# whose roundtrip is *exact* on these dyadic test values (sign * 2^e,
# |e| <= 2, far inside the mantissa budget) — so both wire dtypes must
# reproduce the plain strategy bit-for-bit here, on both backends.
@pytest.mark.parametrize("wire_dtype", ["f32", "fxp32"])
@pytest.mark.parametrize("backend", ["never", "always"])
def test_innet_wires_match_plain_bitwise(wire_dtype, backend):
    (plain,), res_p = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas=backend), "compressed")
    (innet,), res_i = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas=backend,
                            wire_dtype=wire_dtype), "compressed_innet")
    for k in plain:
        assert np.array_equal(plain[k], innet[k]), (wire_dtype, k)
        assert np.array_equal(res_p[k], res_i[k]), (wire_dtype, k)


# The harness mesh has only the (manual) "data" axis, so the region is
# full-manual and the native wire reassembles with a manual-axis
# all_gather.
@pytest.mark.parametrize("wire", ["native", "emulate"])
@pytest.mark.parametrize("backend", ["never", "always"])
def test_rs_wire_paths_match_plain_bitwise(wire, backend):
    (plain,), res_p = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas=backend), "compressed")
    (rs,), res_r = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas=backend, rs_wire=wire),
        "compressed_rs")
    for k in plain:
        assert np.array_equal(plain[k], rs[k]), (wire, k)
        assert np.array_equal(res_p[k], res_r[k]), (wire, k)


# ----------------------------------------------------------------------
# Per-bucket wire plans (PR 6): a mixed plan must be bit-identical to
# the fixed strategies it composes on the buckets it assigns. Single
# worker + dyadic values keep every wire (incl. dense psum of the packed
# f32 stream) exact, so the whole aggregate must equal the fixed
# ``compressed`` run bit-for-bit — outputs AND error-feedback residuals,
# over 3 EF steps. The test tree packs into 6 buckets.
# ----------------------------------------------------------------------

from repro.core.wireplan import WireGroup, WirePlan  # noqa: E402

MIXED_PLANS = {
    "dense+comp+rs": WirePlan(6, (WireGroup(0, 2, "dense"),
                                  WireGroup(2, 2, "compressed"),
                                  WireGroup(4, 2, "compressed_rs"))),
    "innet+comp+dense": WirePlan(6, (WireGroup(0, 3, "compressed_innet"),
                                     WireGroup(3, 1, "compressed"),
                                     WireGroup(4, 2, "dense"))),
    "chunk-override": WirePlan(6, (WireGroup(0, 2, "dense"),
                                   WireGroup(2, 2, "compressed",
                                             stream_chunks=2),
                                   WireGroup(4, 2, "compressed_rs"))),
}


@pytest.mark.parametrize("plan_name", sorted(MIXED_PLANS))
def test_mixed_wire_plan_matches_fixed_bitwise(plan_name):
    cfg = dataclasses.replace(AGG_BASE, use_pallas="never")
    outs_f, res_f = _run_aggregator(cfg, "compressed", steps=3)
    outs_m, res_m = _run_aggregator(cfg, "compressed", steps=3,
                                    wire_plan=MIXED_PLANS[plan_name])
    for step, (of, om) in enumerate(zip(outs_f, outs_m)):
        for k in of:
            assert np.array_equal(of[k], om[k]), (plan_name, step, k)
    for k in res_f:
        assert np.array_equal(res_f[k], res_m[k]), (plan_name, k)


def test_mixed_wire_plan_backend_parity():
    plan = MIXED_PLANS["dense+comp+rs"]
    (out_n,), res_n = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas="never"),
        "compressed", wire_plan=plan)
    (out_a,), res_a = _run_aggregator(
        dataclasses.replace(AGG_BASE, use_pallas="always"),
        "compressed", wire_plan=plan)
    for k in out_n:
        assert np.array_equal(out_n[k], out_a[k]), k
        assert np.array_equal(res_n[k], res_a[k]), k


def test_auto_strategy_matches_compressed_bitwise():
    """The `auto` strategy — explicit mixed plan or its zero-telemetry
    analytic fallback — must reproduce the fixed strategy bit-for-bit
    (the plan only moves buckets between lossless wires)."""
    cfg = dataclasses.replace(AGG_BASE, use_pallas="never")
    outs_f, res_f = _run_aggregator(cfg, "compressed", steps=3)
    for wire_plan in (MIXED_PLANS["dense+comp+rs"], None):
        outs_a, res_a = _run_aggregator(cfg, "auto", steps=3,
                                        wire_plan=wire_plan)
        for step, (of, oa) in enumerate(zip(outs_f, outs_a)):
            for k in of:
                assert np.array_equal(of[k], oa[k]), (wire_plan, step, k)
        for k in res_f:
            assert np.array_equal(res_f[k], res_a[k]), (wire_plan, k)


def test_dense_aggregator_rejects_wire_plan():
    cfg = dataclasses.replace(AGG_BASE, use_pallas="never")
    with pytest.raises(ValueError, match="does not execute wire plans"):
        _run_aggregator(cfg, "dense",
                        wire_plan=MIXED_PLANS["dense+comp+rs"])


def test_compressor_has_no_direct_backend_imports():
    """The dispatch layer is the only compute backend: the compressor
    must not reach into core.sketch/core.peeling directly."""
    import inspect
    import repro.core.compressor as m
    src = inspect.getsource(m)
    for needle in ("encode_blocks", "peel_blocks", "estimate_blocks",
                   "from .sketch", "from .peeling"):
        assert needle not in src, f"compressor bypasses kernels.ops: {needle}"
    assert "from repro.kernels import ops" in src


# The fused wire codec (PR 7): every compressed strategy now funnels
# through ONE producer op before its collectives and ONE consumer op
# after. "always" runs the fused Pallas kernels (interpret mode here);
# "never" runs the composed jnp refs — 3 error-feedback steps must stay
# bit-identical in outputs AND carried residuals, including the fxp32
# innet wire whose dequant is folded into the fused consumer.
@pytest.mark.parametrize("name,wire_dtype",
                         [("compressed", "f32"), ("compressed_rs", "f32"),
                          ("compressed_innet", "f32"),
                          ("compressed_innet", "fxp32")])
def test_fused_wire_parity_over_ef_steps(name, wire_dtype):
    cfg_n = dataclasses.replace(AGG_BASE, use_pallas="never",
                                wire_dtype=wire_dtype)
    cfg_a = dataclasses.replace(AGG_BASE, use_pallas="always",
                                wire_dtype=wire_dtype)
    outs_n, res_n = _run_aggregator(cfg_n, name, steps=3)
    outs_a, res_a = _run_aggregator(cfg_a, name, steps=3)
    for step, (on, oa) in enumerate(zip(outs_n, outs_a)):
        for k in on:
            assert np.array_equal(on[k], oa[k]), (name, step, k)
    for k in res_n:
        assert np.array_equal(res_n[k], res_a[k]), (name, k)


# ----------------------------------------------------------------------
# The all-to-all exchange (PR 8): the compressed permute wire must be
# bit-for-bit the dense one on identical routed payloads — the exchange
# codec runs at ratio 2.5, where sketch capacity exceeds the block even
# when every slot is occupied, so recovery of these dyadic payloads is
# exact. Pinned over 3 steps of evolving payloads, both backends, fused
# and chunked (stream_chunks > 1) lane grids; the multi-rank permute
# legs live in tests/drivers/collectives_driver.py.
# ----------------------------------------------------------------------

from repro.core.aggregators import make_exchange  # noqa: E402

# ratio 2.5 -> group=2, block=256 elems; two blocks per bucket
A2A_BASE = dataclasses.replace(
    BASE, ratio=2.5, topk_ratio=None, error_feedback=False,
    bucket_bytes=2 * 2 * BASE.lanes * 4)


def _a2a_payload(seed):
    r = np.random.default_rng(seed)

    def dyadic(shape, frac):
        n = int(np.prod(shape))
        return dyadic_sparse(n, frac, seed=r.integers(1 << 30)).reshape(shape)

    # leading axis = destination ranks (W=1 here); dense-ish payloads
    # exercise the full-occupancy recovery regime the exchange relies on
    # 825 + 1152 elems -> 4 buckets of 512: divisible by the chunked
    # grid below (the lane grid requires chunk count | bucket count)
    return {"x": dyadic((1, 3 * A2A_BASE.block_elems + 57), 0.9),
            "y": dyadic((1, 18, 64), 0.8)}


def _run_exchange(cfg, name, steps=3):
    mesh = make_mesh((1,), ("data",))
    exchange = make_exchange(name, cfg, mesh, ("data",))

    def fn(payload):
        return exchange(payload)

    jfn = jax.jit(shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), _a2a_payload(0)),),
        out_specs=jax.tree.map(lambda _: P(), exchange_out_struct(cfg)),
        axis_names={"data"}, check_vma=False))
    outs = []
    for s in range(steps):
        payload = jax.tree.map(jnp.asarray, _a2a_payload(seed=s))
        outs.append(jax.tree.map(np.asarray, jfn(payload)))
    return outs


def exchange_out_struct(cfg):
    # merged output drops the destination axis: one slice per leaf
    return {k: v[0] for k, v in _a2a_payload(0).items()}


@pytest.mark.parametrize("backend", ["never", "always"])
@pytest.mark.parametrize("chunks", [None, 2], ids=["fused", "chunked"])
def test_exchange_compressed_matches_dense_bitwise(backend, chunks):
    cfg = dataclasses.replace(A2A_BASE, use_pallas=backend,
                              stream_chunks=chunks)
    outs_d = _run_exchange(cfg, "dense")
    outs_c = _run_exchange(cfg, "compressed")
    for step, (od, oc) in enumerate(zip(outs_d, outs_c)):
        for k in od:
            assert np.array_equal(od[k], oc[k]), (backend, chunks, step, k)
            # W=1: the merge is the identity on the only source's payload
            want = _a2a_payload(seed=step)[k][0]
            np.testing.assert_array_equal(od[k], want, err_msg=str((step, k)))


def test_exchange_backend_parity_bitwise():
    outs = {b: _run_exchange(dataclasses.replace(A2A_BASE, use_pallas=b),
                             "compressed")
            for b in ("never", "always")}
    for step, (on, oa) in enumerate(zip(outs["never"], outs["always"])):
        for k in on:
            assert np.array_equal(on[k], oa[k]), (step, k)


def test_exchange_rejects_bloom_index():
    cfg = dataclasses.replace(A2A_BASE, index="bloom")
    mesh = make_mesh((1,), ("data",))
    exchange = make_exchange("compressed", cfg, mesh, ("data",))
    with pytest.raises(ValueError, match="bitmap"):
        exchange(jax.tree.map(jnp.asarray, _a2a_payload(0)))
