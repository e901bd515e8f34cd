"""Unit coverage for the OR-AllReduce algorithm-selection policy, the
argument validation of the collective primitives, and the per-strategy
wire accounting.

The multi-device semantics (ring == doubling == numpy OR-reduce, the
reduce-scatter chunk placement, native-RS bit-parity) live in
``tests/drivers/collectives_driver.py``; here we pin the *decisions*:
``ring_threshold`` is payload **bytes** (not element count), axes whose
size is not a power of two must take the ring instead of raising from
``or_allreduce_doubling``, a partial ``axis_indices`` dict is a loud
error (silently recomputing ``axis_index`` re-binds outer-shard_map axes
— the Shardy failure the parameter exists to avoid), the psum-emulated
OR is chunk-invariant, and ``compressed_all_reduce`` forwards
``outer_manual`` so fully-manual callers reach the native RS wire.
"""
import dataclasses
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.core import CompressionConfig
from repro.core.collectives import (
    AggregationState, _or_allreduce_psum, _use_ring, compressed_all_reduce,
    init_aggregation_state, or_allreduce, or_reduce_scatter)


def test_threshold_is_bytes_not_elements():
    thr = 65536
    # 16384 uint32 words == 65536 bytes: exactly at the byte threshold
    assert _use_ring(16384 * 4, 4, thr)
    # 16384 *elements* would have crossed an element-count threshold,
    # but it is only 64 KiB-of-4 == under the byte threshold at 16383
    assert not _use_ring(16383 * 4, 4, thr)
    assert not _use_ring(65535, 4, thr)
    assert _use_ring(65536, 4, thr)


@pytest.mark.parametrize("n,ring", [(1, False), (2, False), (3, True),
                                    (4, False), (6, True), (12, True),
                                    (16, False), (24, True)])
def test_non_power_of_two_axes_take_ring(n, ring):
    assert _use_ring(payload_bytes=4, axis_size=n, ring_threshold=1 << 30) \
        == ring


def test_or_allreduce_single_shard_identity():
    # axis size 1 on a trivial mesh context: both branches short-circuit.
    # (No shard_map needed: jax.lax.axis_size is only consulted per axis,
    # and an empty axis list never consults it.)
    x = jnp.asarray(np.arange(8, dtype=np.uint32))
    out = or_allreduce(x, ())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


# ----------------------------------------------------------------------
# axis_indices validation: a partial dict must fail loudly
# ----------------------------------------------------------------------

@pytest.mark.parametrize("fn", [or_allreduce, or_reduce_scatter],
                         ids=["allreduce", "reduce_scatter"])
def test_partial_axis_indices_dict_raises(fn):
    x = jnp.zeros((8,), jnp.uint32)
    with pytest.raises(ValueError, match="axis_indices is missing"):
        fn(x, ("pod", "data"), axis_indices={"pod": jnp.int32(0)})
    # an empty dict over real axes is just as partial
    with pytest.raises(ValueError, match="axis_indices is missing"):
        fn(x, ("data",), axis_indices={})


def test_complete_axis_indices_dict_accepted():
    # validation must not reject a complete dict (axis size 1 context)
    mesh = make_mesh((1,), ("data",))
    x = jnp.asarray(np.arange(8, dtype=np.uint32))

    def f(a):
        idx = {"data": jax.lax.axis_index("data")}
        return (or_allreduce(a, ("data",), axis_indices=idx),
                or_reduce_scatter(a, ("data",), axis_indices=idx))

    ar, rs = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
                               axis_names={"data"}, check_vma=False))(x)
    np.testing.assert_array_equal(np.asarray(ar), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(rs), np.asarray(x))


# ----------------------------------------------------------------------
# chunked psum-emulated OR == unchunked (single-device harness; the
# multi-device parity lives in the collectives driver)
# ----------------------------------------------------------------------

def test_psum_or_emulation_chunk_invariant():
    mesh = make_mesh((1,), ("data",))
    words = np.random.default_rng(3).integers(
        0, 2**32, size=1009, dtype=np.uint32)

    def run(chunk_words):
        return np.asarray(jax.jit(shard_map(
            lambda a: _or_allreduce_psum(a, ("data",),
                                         chunk_words=chunk_words),
            mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names={"data"}, check_vma=False))(jnp.asarray(words)))

    unchunked = run(1 << 30)
    np.testing.assert_array_equal(unchunked, words)  # 1-rank OR == identity
    for chunk in (1, 7, 64, 1008, 1009):
        np.testing.assert_array_equal(run(chunk), unchunked)
    with pytest.raises(ValueError, match="chunk_words"):
        _or_allreduce_psum(jnp.asarray(words), ("data",), chunk_words=0)


# ----------------------------------------------------------------------
# compressed_all_reduce must forward outer_manual (regression: the
# wrapper used to drop it)
# ----------------------------------------------------------------------

def test_compressed_all_reduce_forwards_outer_manual(monkeypatch):
    import repro.core.aggregators as agg_mod
    captured = {}

    def fake_make_aggregator(name, cfg, mesh, dp_axes, tp_axes=("model",),
                             mean=True, outer_manual=None):
        captured.update(name=name, outer_manual=outer_manual)
        return lambda grads, state, specs: (grads, state)

    monkeypatch.setattr(agg_mod, "make_aggregator", fake_make_aggregator)
    cfg = CompressionConfig(ratio=0.5, lanes=8, rows=3)
    grads = {"w": jnp.zeros((4,), jnp.float32)}
    st = AggregationState(residual={"w": jnp.zeros((0,), jnp.float32)})
    compressed_all_reduce(grads, st, {"w": P()}, mesh=None, cfg=cfg,
                          dp_axes=("data",), reduce_scatter=True,
                          outer_manual=("data", "model"))
    assert captured["name"] == "compressed_rs"
    assert captured["outer_manual"] == ("data", "model")


def test_compressed_all_reduce_native_rs_through_wrapper():
    """End-to-end: rs_wire='native' must work through the wrapper when
    the caller declares a full-manual region."""
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6, rounds=10,
                            chunk_blocks=8, rs_wire="native",
                            bucket_bytes=768 * 4)
    mesh = make_mesh((1,), ("data",))
    g = np.zeros(2000, np.float32)
    r = np.random.default_rng(0)
    idx = r.choice(2000, size=100, replace=False)
    g[idx] = r.standard_normal(100).astype(np.float32)
    grads = {"w": jnp.asarray(g)}
    specs = {"w": P()}

    def fn(g):
        st = init_aggregation_state(g, cfg)
        agg, _ = compressed_all_reduce(
            g, st, specs, mesh, cfg, dp_axes=("data",), tp_axes=(),
            reduce_scatter=True, outer_manual=("data",))
        return agg

    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=(specs,),
                            out_specs=specs, axis_names={"data"},
                            check_vma=False))(grads)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(grads["w"]), atol=1e-6)


# ----------------------------------------------------------------------
# per-strategy wire accounting (CompressionConfig.strategy_wire_bytes)
# ----------------------------------------------------------------------

def test_strategy_wire_bytes_native_rs_is_one_over_w():
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6,
                            bucket_bytes=768 * 4)
    W = 4
    # n = whole buckets, a multiple of W: no padding slack at all
    n = cfg.bucket_elems_for(768 * 8) * 8
    acc = cfg.strategy_wire_bytes(n, workers=W, grad_bytes_per_elem=4)
    full = acc["compressed"]["rank_payload_bytes"]
    nat = acc["compressed_rs_native"]["rank_payload_bytes"]
    assert nat * W == full, "native RS payload must be exactly 1/W"
    # emulated RS ships the AllReduce wire
    assert acc["compressed_rs_emulated"] == acc["compressed"]
    # link traffic: the RS ring itself sends half of what the AR ring
    # sends (the no-gather number)
    nat_acc = acc["compressed_rs_native"]
    assert nat_acc["link_bytes_no_gather"] * 2 == \
        acc["compressed"]["link_bytes"]
    # the default (unaligned) accounting ships the recovered-chunk
    # gather too; ZeRO-1-aligned chunk grids skip it entirely
    assert nat_acc["link_bytes"] == nat_acc["link_bytes_with_gather"] \
        == nat_acc["link_bytes_no_gather"] + nat_acc["rs_gather_link_bytes"]
    assert not nat_acc["zero1_aligned"]
    aligned = cfg.strategy_wire_bytes(n, workers=W, grad_bytes_per_elem=4,
                                      zero1_aligned=True)[
        "compressed_rs_native"]
    assert aligned["zero1_aligned"]
    assert aligned["link_bytes"] == aligned["link_bytes_no_gather"]
    assert acc["dense"]["rank_payload_bytes"] == n * 4


def test_strategy_wire_bytes_padding_and_edges():
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6,
                            bucket_bytes=768 * 4)
    # 3 buckets across 4 ranks: padded to 4, payload still strictly below
    # the full AllReduce payload
    n = cfg.bucket_elems_for(768 * 3) * 3
    acc = cfg.strategy_wire_bytes(n, workers=4, grad_bytes_per_elem=4)
    assert acc["compressed_rs_native"]["rank_payload_bytes"] \
        < acc["compressed"]["rank_payload_bytes"]
    # W=1: degenerate but well-defined (no wire at all on the links)
    acc1 = cfg.strategy_wire_bytes(n, workers=1)
    assert acc1["compressed"]["link_bytes"] == 0
    assert acc1["compressed_rs_native"]["link_bytes"] == 0
    with pytest.raises(ValueError, match="workers"):
        cfg.strategy_wire_bytes(n, workers=0)
    # Bloom index cannot be sliced per-rank: no native RS wire entry
    bloom = dataclasses.replace(cfg, index="bloom")
    assert bloom.strategy_wire_bytes(n, workers=4)[
        "compressed_rs_native"] is None


def test_rs_wire_config_validation():
    with pytest.raises(ValueError, match="rs_wire"):
        CompressionConfig(rs_wire="sometimes")
    for ok in ("auto", "native", "emulate"):
        assert CompressionConfig(rs_wire=ok).rs_wire == ok


@pytest.mark.parametrize("workers", [3, 6])
def test_strategy_wire_bytes_padding_non_power_of_two(workers):
    """Non-power-of-two worker counts: the native-RS chunk padding must
    round n_buckets up to the next multiple of W (and ONLY the native
    arm pays it); every other strategy ships the bucket-padded stream
    unpadded. Exact byte accounting, derived independently here."""
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6,
                            bucket_bytes=768 * 4)
    assert cfg.block_elems == 768 and cfg.bucket_quantum == 768
    nb = 7                                    # 7 buckets: ceil(7/3)*3 = 9,
    n = 768 * nb                              # ceil(7/6)*6 = 12
    acc = cfg.strategy_wire_bytes(n, workers=workers, grad_bytes_per_elem=4)

    per_bucket = 768 * 4 + (768 // 32) * 4    # ratio=1 sketch + bitmap
    full = nb * per_bucket
    nb_p = -(-nb // workers) * workers
    ring = 2 * (workers - 1) / workers
    rs = (workers - 1) / workers

    assert acc["dense"]["rank_payload_bytes"] == n * 4
    assert acc["dense"]["link_bytes"] == int(n * 4 * ring)
    assert acc["compressed"]["rank_payload_bytes"] == full
    assert acc["compressed"]["link_bytes"] == int(full * ring)
    assert acc["compressed_rs_emulated"] == acc["compressed"]
    nat = acc["compressed_rs_native"]
    assert nat["rank_payload_bytes"] == nb_p * per_bucket // workers
    assert nat["link_bytes_no_gather"] == int(nb_p * per_bucket * rs)
    assert nat["rs_gather_link_bytes"] == int(nb_p * 768 * 4 * rs)
    assert nat["link_bytes"] == \
        nat["link_bytes_no_gather"] + nat["rs_gather_link_bytes"]
    # chunk padding never erases the win for this bucket count
    assert nat["rank_payload_bytes"] < full
    # innet: bucket-padded stream once up the tree, no chunk padding;
    # fxp32 additionally ships one int32 exponent per bucket
    innet = acc["compressed_innet"]
    assert innet["rank_payload_bytes"] == full
    assert innet["link_bytes"] == full
    assert innet["root_link_bytes"] == full
    assert innet["exponent_bytes"] == 0
    fx = dataclasses.replace(cfg, wire_dtype="fxp32")
    innet_fx = fx.strategy_wire_bytes(n, workers,
                                      grad_bytes_per_elem=4)[
        "compressed_innet"]
    assert innet_fx["exponent_bytes"] == nb * 4
    assert innet_fx["rank_payload_bytes"] == full + nb * 4
    assert innet_fx["root_link_bytes"] == full + nb * 4
    # the tree's hottest link beats every ring link at W >= 3
    assert innet_fx["root_link_bytes"] < acc["compressed"]["link_bytes"]
    assert innet_fx["root_link_bytes"] < acc["dense"]["link_bytes"]


def test_strategy_wire_bytes_innet_single_worker_no_wire():
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6,
                            bucket_bytes=768 * 4, wire_dtype="fxp32")
    acc = cfg.strategy_wire_bytes(768 * 2, workers=1)
    assert acc["compressed_innet"]["link_bytes"] == 0
    assert acc["compressed_innet"]["root_link_bytes"] == 0
    # the aggregate a rank holds is still the full (metadata-bearing) one
    assert acc["compressed_innet"]["rank_payload_bytes"] > 0


# ----------------------------------------------------------------------
# make_aggregator: unknown strategies must name the valid ones
# ----------------------------------------------------------------------

def test_make_aggregator_unknown_strategy_names_valid_ones():
    from repro.core.aggregators import AGGREGATORS, make_aggregator
    cfg = CompressionConfig(ratio=0.5, lanes=8, rows=3)
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError) as ei:
        make_aggregator("compresed", cfg, mesh, ("data",))
    msg = str(ei.value)
    assert "compresed" in msg
    for name in ("dense", "compressed", "compressed_rs",
                 "compressed_innet", "auto"):
        assert name in msg, f"error message should offer {name!r}: {msg}"
    assert set(AGGREGATORS) == {"dense", "compressed", "compressed_rs",
                                "compressed_innet", "auto"}


# ----------------------------------------------------------------------
# cfg.overlap is honored on EVERY wire now (PR 5): constructing and
# running the native-RS / innet strategies with overlap must stay
# silent (the PR 4 one-time "overlap ignored" warnings are retired;
# unsatisfiable chunk grids raise ValueError from core/streams.py
# naming the alignment constraint — see tests/test_streams.py).
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["compressed_rs", "compressed_innet"])
def test_overlap_is_honored_without_warning(name):
    from repro.core.aggregators import make_aggregator
    cfg = CompressionConfig(ratio=1.0, lanes=128, rows=6, overlap=True,
                            bucket_bytes=768 * 4, switch_slots=1)
    fused = dataclasses.replace(cfg, overlap=False)
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.asarray(
        np.linspace(-2.0, 2.0, 3 * 768, dtype=np.float32))}
    specs = {"w": P()}

    def run(c):
        agg = make_aggregator(name, c, mesh, ("data",), (),
                              outer_manual=("data",))

        def fn(g, r):
            out, st = agg(g, AggregationState(residual=r), specs)
            return out

        jfn = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(specs, specs), out_specs=specs,
            axis_names={"data"}, check_vma=False))
        return np.asarray(jfn(tree, init_aggregation_state(
            tree, c).residual)["w"])

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        got = run(cfg)
    assert np.array_equal(got, run(fused)), \
        "overlapped schedule diverged from the fused wire"
