"""Unit coverage for the wire-chunk scheduling layer (core/streams.py).

The aggregator-level guarantees (chunked == unchunked bit-for-bit over 3
EF steps, for all four strategies, on the real multi-device wires
including the gather-skip path) live in ``tests/test_dispatch.py`` and
``tests/drivers/collectives_driver.py``; here we pin the *grid rules*:

- chunk grids align to whole buckets, zero-padding non-divisible counts;
- a forced ``stream_chunks`` that would split a per-rank reduce-scatter
  boundary, or an in-network switch window, raises ``ValueError``
  *naming the alignment constraint* (never a silent fallback — the PR 4
  warning behaviour this layer retired);
- :func:`stream_schedule` is a pure reordering: bit-identical to the
  direct per-chunk loop;
- the ZeRO-1 gather-skip predicate fires exactly when every leaf's
  per-rank optimizer slice sits inside that rank's owned chunk slices,
  using the same ``zero_slice_dim`` rule the train step slices with.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import CompressionConfig
from repro.core.bucketing import make_bucket_plan
from repro.core.streams import (StreamPlan, make_stream_plan,
                                stream_schedule, zero1_gather_skip,
                                zero_slice_dim)

# block_elems = 768; one bucket = one block
CFG = CompressionConfig(ratio=1.0, lanes=128, rows=6, bucket_bytes=768 * 4)


def _plan(n_buckets):
    return make_bucket_plan({"w": np.zeros(768 * n_buckets, np.float32)},
                            CFG)


# ----------------------------------------------------------------------
# grid resolution
# ----------------------------------------------------------------------

def test_fused_grid_is_one_chunk():
    splan = make_stream_plan(_plan(5), CFG)
    assert (splan.n_chunks, splan.chunk_buckets) == (1, 5)
    assert not splan.streamed and splan.pad_buckets == 0


def test_overlap_defaults_to_per_bucket_on_the_allreduce_wire():
    cfg = dataclasses.replace(CFG, overlap=True)
    splan = make_stream_plan(_plan(5), cfg)
    assert (splan.n_chunks, splan.chunk_buckets) == (5, 1)


def test_non_divisible_chunk_count_zero_pads():
    cfg = dataclasses.replace(CFG, stream_chunks=3)
    splan = make_stream_plan(_plan(5), cfg)
    assert (splan.n_chunks, splan.chunk_buckets) == (3, 2)
    assert splan.pad_buckets == 1
    buckets = jnp.arange(5 * 768, dtype=jnp.float32).reshape(5, 768)
    chunks = splan.chunk_view(buckets)
    assert chunks.shape == (3, 2, 768)
    assert not np.asarray(chunks[2, 1]).any()          # zero pad bucket
    np.testing.assert_array_equal(
        np.asarray(chunks).reshape(-1)[:5 * 768],
        np.asarray(buckets).reshape(-1))


def test_stream_chunks_clamps_to_bucket_count():
    cfg = dataclasses.replace(CFG, stream_chunks=99)
    assert make_stream_plan(_plan(5), cfg).n_chunks == 5


def test_empty_chunks_shrink_to_covering_grid():
    """A grid whose tail chunks would be ALL zero-padding shrinks to the
    largest count that still covers the stream — empty chunks would
    spend real collective rounds on all-zero payloads."""
    # AllReduce: 4 chunks of ceil(5/4)=2 buckets -> chunk 4 all padding
    splan = make_stream_plan(_plan(5),
                             dataclasses.replace(CFG, stream_chunks=4))
    assert (splan.n_chunks, splan.chunk_buckets) == (3, 2)
    # window grid: 3 chunks x 2 windows over ceil(7/2)=4 windows ->
    # chunk 3 (buckets 8..11) would be pure padding
    splan = make_stream_plan(_plan(7),
                             dataclasses.replace(CFG, stream_chunks=3),
                             window_buckets=2)
    assert (splan.n_chunks, splan.chunk_buckets) == (2, 4)
    # scatter grids can never go empty (chunk padding is < W while every
    # chunk spans >= W buckets): W=8, nb=9 keeps both 8-bucket chunks
    splan = make_stream_plan(_plan(9),
                             dataclasses.replace(CFG, stream_chunks=2),
                             workers=8, scatter=True)
    assert (splan.n_chunks, splan.chunk_buckets) == (2, 8)
    assert splan.pad_buckets < splan.chunk_buckets


def test_rs_grid_defaults_to_per_rank_chunks():
    cfg = dataclasses.replace(CFG, overlap=True)
    splan = make_stream_plan(_plan(5), cfg, workers=4, scatter=True)
    # per_rank = ceil(5/4) = 2 -> 2 chunks of 4 buckets (1 per rank each)
    assert (splan.n_chunks, splan.chunk_buckets) == (2, 4)
    assert splan.rank_chunk_buckets == 1
    assert splan.pad_buckets == 3
    # rank r owns bucket r of each chunk
    assert splan.rank_intervals(1) == ((768, 2 * 768),
                                       (4 * 768 + 768, 4 * 768 + 2 * 768))


def test_rs_boundary_splitting_chunks_raise_naming_the_constraint():
    cfg = dataclasses.replace(CFG, stream_chunks=3)
    with pytest.raises(ValueError) as ei:
        make_stream_plan(_plan(5), cfg, workers=4, scatter=True)
    msg = str(ei.value)
    assert "per-rank" in msg and "ceil(n_buckets/W)" in msg
    assert "ceil(5/4) = 2" in msg


def test_innet_grid_spans_whole_switch_windows():
    cfg = dataclasses.replace(CFG, overlap=True, switch_slots=2)
    splan = make_stream_plan(_plan(5), cfg, window_buckets=2)
    assert (splan.n_chunks, splan.chunk_buckets) == (3, 2)
    # a coarser explicit grid still spans whole windows
    cfg2 = dataclasses.replace(CFG, stream_chunks=2)
    splan2 = make_stream_plan(_plan(5), cfg2, window_buckets=2)
    assert (splan2.n_chunks, splan2.chunk_buckets) == (2, 4)


def test_innet_window_splitting_chunks_raise_naming_switch_slots():
    cfg = dataclasses.replace(CFG, stream_chunks=4)
    with pytest.raises(ValueError, match="switch_slots"):
        make_stream_plan(_plan(5), cfg, window_buckets=8)  # 1 window


def test_stream_plan_validates_geometry():
    with pytest.raises(ValueError, match="workers"):
        make_stream_plan(_plan(2), CFG, workers=0)
    with pytest.raises(ValueError, match="divisible"):
        StreamPlan(n_buckets=4, bucket_elems=768, blocks_per_bucket=1,
                   words_per_bucket=24, workers=3, n_chunks=1,
                   chunk_buckets=4)
    with pytest.raises(ValueError, match="covers"):
        StreamPlan(n_buckets=4, bucket_elems=768, blocks_per_bucket=1,
                   words_per_bucket=24, workers=1, n_chunks=1,
                   chunk_buckets=2)


# ----------------------------------------------------------------------
# the pipeline driver
# ----------------------------------------------------------------------

def test_stream_schedule_matches_direct_loop_bitwise():
    xs = jnp.asarray(
        np.random.default_rng(0).standard_normal((6, 32)).astype(np.float32))

    def encode(i, x):
        return x * 2.0 + i.astype(jnp.float32), x - 1.0

    def reduce(payload):
        a, b = payload
        return a + b, a * b

    got = jax.jit(lambda v: stream_schedule(v, encode, reduce))(xs)
    want = [reduce(encode(jnp.int32(i), xs[i])) for i in range(6)]
    for j in range(2):
        np.testing.assert_array_equal(
            np.asarray(got[j]), np.stack([np.asarray(w[j]) for w in want]))


def test_stream_schedule_single_chunk():
    xs = jnp.ones((1, 4))
    got = stream_schedule(xs, lambda i, x: x + 1.0, lambda p: p * 3.0)
    np.testing.assert_array_equal(np.asarray(got), np.full((1, 4), 6.0))


# ----------------------------------------------------------------------
# ZeRO-1 alignment
# ----------------------------------------------------------------------

def test_zero_slice_dim_rule():
    assert zero_slice_dim((8,), P(), 4) == 0
    assert zero_slice_dim((2, 8), P(), 4) == 1          # largest wins
    assert zero_slice_dim((8, 8), P(None, "model"), 4) == 0   # sharded out
    assert zero_slice_dim((3, 5), P(), 4) is None


def _skip_case(shapes, zero1_dims, n_chunks, workers=4):
    tree = {f"l{i}": np.zeros(sh, np.float32)
            for i, sh in enumerate(shapes)}
    plan = make_bucket_plan(tree, CFG)
    cfg = dataclasses.replace(CFG, stream_chunks=n_chunks)
    splan = make_stream_plan(plan, cfg, workers=workers, scatter=True)
    return zero1_gather_skip(splan, plan, zero1_dims)


def test_gather_skip_fires_on_aligned_chunk_grid():
    # two leaves of 4 buckets each (8-bucket stream); W=4, per_rank=2,
    # 2 chunks of 4 buckets -> rank r owns bucket r of each chunk, which
    # is exactly each leaf's dim-0 ZeRO-1 slice r.
    assert _skip_case([(4 * 768,), (4 * 768,)], (0, 0), n_chunks=2)
    # leading size-1 dims keep the slice flat-contiguous
    assert _skip_case([(1, 4 * 768), (4 * 768,)], (1, 0), n_chunks=2)


def test_gather_skip_rejects_misaligned_grids_and_leaves():
    # one fused chunk: rank ownership is two whole buckets per rank —
    # leaf 2's slices land on the wrong ranks
    assert not _skip_case([(4 * 768,), (4 * 768,)], (0, 0), n_chunks=1)
    # a leaf with no ZeRO-1 slice dim disables the skip outright
    assert not _skip_case([(4 * 768,), (4 * 768,)], (0, None), n_chunks=2)
    # slice dim with a real (non-1) leading dim is not flat-contiguous
    assert not _skip_case([(2, 2 * 768), (4 * 768,)], (1, 0), n_chunks=2)
    # leaf sizes not divisible by W
    assert not _skip_case([(4 * 768 + 4,), (4 * 768 - 4,)], (0, 0),
                          n_chunks=2)
    # single worker / missing dims: trivially off
    assert not _skip_case([(4 * 768,)], (0,), n_chunks=1, workers=1)
    plan = make_bucket_plan({"w": np.zeros(8 * 768, np.float32)}, CFG)
    splan = make_stream_plan(plan, dataclasses.replace(CFG, stream_chunks=2),
                             workers=4, scatter=True)
    assert not zero1_gather_skip(splan, plan, None)


def test_gather_skip_guard_keys_off_actual_leaf_sharding():
    """The nested-packing guard must look at whether any leaf is really
    sharded on a non-DP axis — NOT at which axes the mesh merely has:
    a pure-DP profile on a mesh that also carries a (unused) model axis
    must still get the skip."""
    from repro.core.aggregators import make_aggregator

    class FakeMesh:  # shape/axis_names are all the aggregator reads
        shape = {"data": 4, "model": 2}
        axis_names = ("data", "model")

    cfg = dataclasses.replace(CFG, rs_wire="native", stream_chunks=2)
    agg = make_aggregator("compressed_rs", cfg, FakeMesh(), ("data",), (),
                          outer_manual=("data", "model"),
                          zero1_dims=(0, 0))
    tree = {"a": np.zeros(4 * 768, np.float32),
            "b": np.zeros(4 * 768, np.float32)}
    repl = {"a": P(), "b": P()}
    tp = {"a": P("model"), "b": P()}
    # replicated leaves: the packed stream is the global view
    assert agg.gather_skip_active(tree, repl)
    # a genuinely TP-sharded leaf packs a TP-local stream -> the
    # alignment math is invalid, keep the gather
    assert not agg.gather_skip_active(tree, tp)


# ----------------------------------------------------------------------
# wire accounting picks the gather side by alignment
# ----------------------------------------------------------------------

def test_strategy_wire_bytes_gather_skip_side():
    n = 8 * 768
    base = CFG.strategy_wire_bytes(n, workers=4, grad_bytes_per_elem=4)
    nat = base["compressed_rs_native"]
    assert nat["link_bytes"] == nat["link_bytes_with_gather"]
    aligned = CFG.strategy_wire_bytes(
        n, workers=4, grad_bytes_per_elem=4, zero1_aligned=True)[
        "compressed_rs_native"]
    assert aligned["link_bytes"] == aligned["link_bytes_no_gather"]
    assert aligned["link_bytes"] < nat["link_bytes"]
