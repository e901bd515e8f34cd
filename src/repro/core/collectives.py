"""Aggregation collectives for the compressed wire format.

The paper aggregates ``S(X) = [Y, B]`` through "the existing aggregation
API" — NCCL sum for ``Y`` and switch/NCCL OR for ``B``. On a TPU mesh the
sum is ``jax.lax.psum``; OR is *not* a native ICI reduction, so we build an
OR-AllReduce out of ``jax.lax.ppermute``:

- ``or_allreduce_ring``     — reduce-scatter + all-gather ring with a
  bitwise-OR combiner; bandwidth-optimal (2·(W−1)/W · |B| per link), the
  analogue of NCCL's ring AllReduce.
- ``or_allreduce_doubling`` — recursive doubling (log2 W full-size steps);
  latency-optimal for small bitmaps, used when |B|/W would be tiny.
- ``or_allreduce``          — hierarchical driver: ring within a pod (ICI),
  then doubling across pods (DCN has few, fat hops), then a broadcast-free
  second ring phase. Payloads at or above ``ring_threshold`` *bytes* (and
  any axis whose size is not a power of two) take the ring; small
  power-of-two axes take recursive doubling.
- ``or_reduce_scatter``     — phase 1 of the ring alone: after the
  reduce-scatter each rank holds only its own fully OR-reduced 1/W chunk,
  (W−1)/W · |B| per link and no all-gather phase. This is the bitmap leg
  of the native reduce-scatter wire path (PR 3): the sketch reduces with
  ``jax.lax.psum_scatter`` and the bitmap with this primitive, so the
  reduced payload that lands on each rank is 1/W of the AllReduce
  strategies' — see
  :class:`repro.core.aggregators.CompressedReduceScatterAggregator` and
  ``CompressionConfig.strategy_wire_bytes``.

All functions must run inside ``shard_map`` where ``axis_name`` is manual.

Since PR 2 this module holds only the **primitives** (plus the dense
baseline and the error-feedback state container). Gradient aggregation
itself is a pluggable strategy over fixed-size buckets — ONE sketch
encode, ONE stacked sketch-``psum`` and ONE OR-AllReduce for the whole
pytree instead of a per-leaf Python loop — implemented in
:mod:`repro.core.aggregators` on top of :mod:`repro.core.bucketing`.
:func:`compressed_all_reduce` survives as a thin compatibility wrapper
over the bucketed :class:`~repro.core.aggregators.CompressedAggregator`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from .config import CompressionConfig


# ----------------------------------------------------------------------
# OR-AllReduce primitives (manual collectives)
# ----------------------------------------------------------------------

def _ring_perm(n: int):
    return [(i, (i + 1) % n) for i in range(n)]


def linear_rank(axis_names: Sequence[str],
                axis_indices: Optional[dict] = None) -> jnp.ndarray:
    """This shard's rank-major linear index over ``axis_names``.

    ``rank = (((i0) * s1 + i1) * s2 + i2) ...`` with the first axis most
    significant — the chunk-to-rank order of ``jax.lax.psum_scatter`` /
    tiled ``all_gather`` over the same axis tuple, of
    :func:`or_reduce_scatter`, and of the peel's per-rank
    ``block_offset``. Every site that linearizes mesh axes must use this
    helper so the orders can never drift apart. ``axis_indices``: as in
    :func:`or_allreduce_ring` (required complete if given).
    """
    _check_axis_indices(axis_names, axis_indices)
    rank = jnp.int32(0)
    for ax in axis_names:
        idx = axis_indices[ax] if axis_indices else jax.lax.axis_index(ax)
        rank = rank * jax.lax.axis_size(ax) + idx
    return rank


def or_allreduce_ring(x: jnp.ndarray, axis_name: str,
                      idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """Bitwise-OR AllReduce via a bandwidth-optimal ring (RS + AG).

    ``x``: uint32 words, identical shape on every shard of ``axis_name``.
    ``idx``: this shard's index on ``axis_name``. Pass it in when calling
    from a *nested* shard_map — ``axis_index`` on an axis bound by an
    outer shard_map trips the Shardy verifier (re-binding), while plain
    ppermute/psum on outer axes are fine.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if idx is None:
        idx = jax.lax.axis_index(axis_name)
    size = x.shape[0]
    pad = (-size) % n
    chunks = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)
                     ).reshape((n, (size + pad) // n) + x.shape[1:])
    perm = _ring_perm(n)

    # Phase 1 — reduce-scatter: after n-1 steps, shard i owns the fully
    # OR-reduced chunk (i+1) mod n.
    for t in range(n - 1):
        send = jax.lax.dynamic_index_in_dim(chunks, (idx - t) % n, 0, keepdims=False)
        recv = jax.lax.ppermute(send, axis_name, perm)
        tgt = (idx - t - 1) % n
        upd = jax.lax.dynamic_index_in_dim(chunks, tgt, 0, keepdims=False) | recv
        chunks = jax.lax.dynamic_update_index_in_dim(chunks, upd, tgt, 0)

    # Phase 2 — all-gather of the reduced chunks around the same ring.
    for t in range(n - 1):
        send = jax.lax.dynamic_index_in_dim(chunks, (idx + 1 - t) % n, 0, keepdims=False)
        recv = jax.lax.ppermute(send, axis_name, perm)
        tgt = (idx - t) % n
        chunks = jax.lax.dynamic_update_index_in_dim(chunks, recv, tgt, 0)

    out = chunks.reshape((size + pad,) + x.shape[1:])
    return out[:size] if pad else out


def or_reduce_scatter_ring(x: jnp.ndarray, axis_name: str,
                           idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """Bitwise-OR Reduce-Scatter via the ring's phase 1 alone.

    Returns this rank's fully OR-reduced chunk ``x[idx*C:(idx+1)*C]``
    with ``C = x.shape[0] // n`` — the chunk-to-rank assignment matches
    ``jax.lax.psum_scatter(..., scatter_dimension=0, tiled=True)``, so
    the sketch (psum_scatter) and the bitmap (this ring) arrive sliced
    identically. ``x.shape[0]`` must divide evenly by the axis size (the
    bucketed callers pad to whole per-rank chunks first).

    The send schedule is the reduce-scatter ring shifted so the chunk a
    rank finishes reducing at step n-2 is its *own* chunk ``idx`` (the
    AllReduce ring in :func:`or_allreduce_ring` finishes on chunk
    ``(idx+1) % n``, which only matters there because phase 2 regathers
    everything). ``idx``: see :func:`or_allreduce_ring`.
    """
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] % n:
        raise ValueError(
            f"or_reduce_scatter: leading dim {x.shape[0]} not divisible "
            f"by axis {axis_name!r} size {n}")
    if n == 1:
        return x
    if idx is None:
        idx = jax.lax.axis_index(axis_name)
    chunks = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    perm = _ring_perm(n)
    for t in range(n - 1):
        send = jax.lax.dynamic_index_in_dim(chunks, (idx - t - 1) % n, 0,
                                            keepdims=False)
        recv = jax.lax.ppermute(send, axis_name, perm)
        tgt = (idx - t - 2) % n
        upd = jax.lax.dynamic_index_in_dim(chunks, tgt, 0,
                                           keepdims=False) | recv
        chunks = jax.lax.dynamic_update_index_in_dim(chunks, upd, tgt, 0)
    return jax.lax.dynamic_index_in_dim(chunks, idx, 0, keepdims=False)


def or_allreduce_doubling(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Bitwise-OR AllReduce via recursive doubling (requires power-of-2)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError(f"recursive doubling needs power-of-2 size, got {n}")
    d = 1
    while d < n:
        perm = [(i, i ^ d) for i in range(n)]
        x = x | jax.lax.ppermute(x, axis_name, perm)
        d *= 2
    return x


# Words per chunk of the psum-emulated OR. The 32-way int32 bit-unpack
# (and the psum'd counts) are 64x the bytes of the uint32 words they
# cover, so a one-shot unpack of a large bitmap transiently costs ~128x
# the bitmap; chunking bounds the peak at ~8 MiB per chunk.
PSUM_OR_CHUNK_WORDS = 1 << 16


def _or_allreduce_psum(x: jnp.ndarray, axis_names: Sequence[str],
                       chunk_words: int = PSUM_OR_CHUNK_WORDS) -> jnp.ndarray:
    """OR-AllReduce emulated with the sum collective (exact).

    Unpacks each uint32 word into its 32 bits, psums the bit counts, and
    repacks ``count > 0``. 32x the wire volume of the native OR — this is
    the path for callers that cannot use the ppermute ring (a multi-axis
    all-to-all, or ``tree_all_reduce(use_ppermute=False)``).

    The unpack/psum runs in chunks of ``chunk_words`` leading-dim words
    (one psum per chunk, a static Python loop) so the int32 bit-unpack
    transient is bounded at ~128 bytes x ``chunk_words`` instead of 128x
    the whole bitmap. Bit-exact regardless of chunking: each word's 32
    counts are independent.
    """
    if chunk_words < 1:
        raise ValueError(f"chunk_words must be >= 1, got {chunk_words}")
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def one(xc):
        bits = ((xc[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
        counts = jax.lax.psum(bits, tuple(axis_names))
        return jnp.sum(
            jnp.where(counts > 0, jnp.uint32(1) << shifts, jnp.uint32(0)),
            axis=-1, dtype=jnp.uint32)

    n = x.shape[0] if x.ndim else 0
    if x.ndim == 0 or n <= chunk_words:
        return one(x)
    parts = [one(x[i:i + chunk_words]) for i in range(0, n, chunk_words)]
    return jnp.concatenate(parts, axis=0)


def _use_ring(payload_bytes: int, axis_size: int, ring_threshold: int) -> bool:
    """Ring vs recursive doubling: ring for payloads of ``ring_threshold``
    bytes or more (bandwidth-bound regime), and always for axis sizes
    that are not a power of two (doubling requires 2^k participants)."""
    return payload_bytes >= ring_threshold or bool(axis_size & (axis_size - 1))


def _check_axis_indices(axis_names: Sequence[str],
                        axis_indices: Optional[dict]) -> None:
    """A *partial* ``axis_indices`` dict is always a caller bug: falling
    back to ``axis_index`` for the missing axes would re-bind an axis
    already bound by an outer shard_map inside the nested region — the
    exact Shardy failure the parameter exists to avoid. Fail loudly
    instead of silently recomputing."""
    if axis_indices is None:
        return
    missing = [ax for ax in axis_names if ax not in axis_indices]
    if missing:
        raise ValueError(
            f"axis_indices is missing {missing} (has "
            f"{sorted(axis_indices)}); pass every reduced axis's index "
            "or None — a partial dict would silently re-bind axis_index "
            "inside a nested shard_map region")


def or_allreduce(x: jnp.ndarray, axis_names: Sequence[str],
                 ring_threshold: int = 65536,
                 axis_indices: Optional[dict] = None) -> jnp.ndarray:
    """Hierarchical OR-AllReduce over several (manual) mesh axes.

    Axes are reduced innermost-first (e.g. ``("pod", "data")`` rings over
    ``data`` within each pod, then combines across pods).

    ``ring_threshold``: payload size in **bytes** at or above which the
    bandwidth-optimal ring is used; smaller payloads take recursive
    doubling to dodge ring latency. Axes whose size is not a power of two
    always take the ring (doubling requires power-of-2 participants).

    ``axis_indices``: {axis: this shard's index} — required when calling
    from a nested shard_map (see or_allreduce_ring). If given it must
    cover *every* axis in ``axis_names`` (ValueError otherwise).
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    _check_axis_indices(axis_names, axis_indices)
    payload_bytes = x.size * x.dtype.itemsize
    for ax in reversed(tuple(axis_names)):
        if _use_ring(payload_bytes, jax.lax.axis_size(ax), ring_threshold):
            idx = axis_indices[ax] if axis_indices else None
            x = or_allreduce_ring(x, ax, idx=idx)
        else:
            x = or_allreduce_doubling(x, ax)
    return x


def or_reduce_scatter(x: jnp.ndarray, axis_names: Sequence[str],
                      axis_indices: Optional[dict] = None) -> jnp.ndarray:
    """Hierarchical bitwise-OR Reduce-Scatter over (manual) mesh axes.

    Each rank receives only its own fully OR-reduced ``1/W`` chunk of
    ``x`` (leading dim, which must divide by the total axis size W).
    Chunk-to-rank assignment is rank-major in ``axis_names`` order —
    identical to ``jax.lax.psum_scatter(x, tuple(axis_names),
    scatter_dimension=0, tiled=True)`` — so axes scatter
    *outermost*-first: the outer axis picks the coarse chunk, each inner
    axis a sub-chunk of it. (The AllReduce driver reduces innermost-first
    instead; order is irrelevant there because everyone ends with
    everything.)
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    _check_axis_indices(axis_names, axis_indices)
    W = 1
    for ax in axis_names:
        W *= jax.lax.axis_size(ax)
    if x.shape[0] % W:
        raise ValueError(
            f"or_reduce_scatter: leading dim {x.shape[0]} not divisible "
            f"by the total axis size {W}")
    for ax in axis_names:
        idx = axis_indices[ax] if axis_indices else None
        x = or_reduce_scatter_ring(x, ax, idx=idx)
    return x


def gather_chunk_slices(local: jnp.ndarray, axis_names: Sequence[str],
                        axis_indices: Optional[dict] = None,
                        use_all_gather: bool = True) -> jnp.ndarray:
    """Reassemble per-chunk reduce-scatter slices across ranks.

    The inverse of a *per-chunk* ``psum_scatter`` / :func:`or_reduce_scatter`
    schedule (the streamed native RS wire, see :mod:`repro.core.streams`):
    ``local`` is ``(n_chunks, S, ...)`` — this rank's fully-reduced slice
    of each wire chunk.  Returns ``(n_chunks, W * S, ...)`` where every
    chunk's leading dim is the rank-major concatenation of all ranks'
    slices, i.e. chunk ``j`` restored exactly as the one-shot wire would
    have delivered it.  One collective for all chunks.

    ``use_all_gather=True`` (full-manual regions, and new-JAX
    partial-auto) uses a manual-axis ``all_gather``; ``False`` keeps the
    zero-pad + ``psum`` ZeRO-1 gather trick for partial-auto regions
    where Shardy would un-shard the auto TP axes around a manual-axis
    all_gather (2x the all_gather ring's wire, bit-identical values —
    each slice lands exactly once either way).
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    _check_axis_indices(axis_names, axis_indices)
    W = 1
    for ax in axis_names:
        W *= jax.lax.axis_size(ax)
    if W == 1:
        return local
    n_chunks, s = local.shape[0], local.shape[1]
    if use_all_gather:
        # (W, n_chunks, S, ...) stacked rank-major over the axis tuple,
        # the same linearization as linear_rank / psum_scatter tiling.
        ag = jax.lax.all_gather(local, axis_names, axis=0, tiled=False)
        if ag.ndim == local.ndim + len(axis_names):
            # multi-axis all_gather stacks one dim per axis (outer axis
            # first == rank-major): merge them into the single W dim
            ag = ag.reshape((W,) + local.shape)
        perm = (1, 0, 2) + tuple(range(3, ag.ndim))
        return ag.transpose(perm).reshape(
            (n_chunks, W * s) + local.shape[2:])
    rank = linear_rank(axis_names, axis_indices)
    full = jnp.zeros((n_chunks, W * s) + local.shape[2:], local.dtype)
    full = jax.lax.dynamic_update_slice_in_dim(full, local, rank * s, axis=1)
    return jax.lax.psum(full, axis_names)


# ----------------------------------------------------------------------
# All-to-all lane merge (the permute pattern, PR 8)
# ----------------------------------------------------------------------

def alltoall_lane_sum(x: jnp.ndarray, axis_names: Sequence[str],
                       axis_indices: Optional[dict] = None,
                       combine: str = "add") -> jnp.ndarray:
    """Merge stacked all-to-all lanes: rank ``r`` receives
    ``combine_s x_s[r]`` over all source ranks ``s``.

    ``x``: ``(W, ...)`` — lane ``d`` is this rank's payload destined for
    rank ``d``, rank-major over ``axis_names`` (:func:`linear_rank`
    order).  The merge at the receiving rank IS the homomorphic
    aggregation: the sum of sketches (``combine="add"``) / OR of bitmaps
    (``combine="or"``) of every source's payload for this rank.

    Native wire: ``W - 1`` ppermutes — offset ``k`` ships lane
    ``(i + k) % W`` from every source ``i`` to rank ``(i + k) % W``, so
    each rank sends/receives ``(W-1)/W`` of its stacked payload (the
    all-to-all wire model in ``CompressionConfig.strategy_wire_bytes``).
    Single manual axis only (ppermute takes one axis name).

    Emulation (multi-axis EP): reduce the whole ``(W, ...)`` stack —
    psum for ``add``, the psum-based OR for ``or`` — then slice this
    rank's lane.  Correct, but ships the ring AllReduce volume (and 32x
    on the bitmap).
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    _check_axis_indices(axis_names, axis_indices)
    if combine not in ("add", "or"):
        raise ValueError(f"combine must be 'add' or 'or', got {combine!r}")
    W = 1
    for ax in axis_names:
        W *= jax.lax.axis_size(ax)
    if x.shape[0] != W:
        raise ValueError(
            f"all-to-all payload has {x.shape[0]} lanes but the axis "
            f"tuple {tuple(axis_names)} has {W} ranks")
    if W == 1:
        return x[0]
    if len(axis_names) == 1:
        ax = axis_names[0]
        idx = axis_indices[ax] if axis_indices else jax.lax.axis_index(ax)
        out = jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False)
        for k in range(1, W):
            perm = [(i, (i + k) % W) for i in range(W)]
            send = jax.lax.dynamic_index_in_dim(x, (idx + k) % W, 0,
                                                keepdims=False)
            recv = jax.lax.ppermute(send, ax, perm)
            out = (out | recv) if combine == "or" else (out + recv)
        return out
    if combine == "or":
        full = _or_allreduce_psum(x, axis_names)
    else:
        full = jax.lax.psum(x, axis_names)
    rank = linear_rank(axis_names, axis_indices)
    return jax.lax.dynamic_index_in_dim(full, rank, 0, keepdims=False)


def sketch_all_to_all(sketches: jnp.ndarray, words: jnp.ndarray,
                      axis_names: Sequence[str],
                      axis_indices: Optional[dict] = None):
    """Compressed expert-parallel all-to-all: ship per-destination sketch
    lanes over the permute wire and merge them homomorphically at the
    receiving rank (PR 8).

    ``sketches``: ``(W, *sketch_shape)`` float lanes — lane ``d`` is the
    sketch of this rank's payload destined for rank ``d``.
    ``words``: ``(W, n_words)`` uint32 bitmap lanes, ditto.

    Returns ``(sketch, words)`` — this rank's merged lane: the *sum* of
    every source's sketch for it and the *OR* of their bitmaps, i.e.
    exactly the compressed form of ``sum_s payload_s[this_rank]``.  The
    merge happens on the wire (ppermute-accumulate) — there is no
    barrier and no full gather, the ScaleCom/THC point that the
    homomorphic combine must land at the receiving expert.

    The native path needs a single manual axis; multi-axis EP takes the
    psum-emulation fallback (see :func:`alltoall_lane_sum`).
    """
    sk = alltoall_lane_sum(sketches, axis_names, axis_indices=axis_indices,
                            combine="add")
    wd = alltoall_lane_sum(words, axis_names, axis_indices=axis_indices,
                            combine="or")
    return sk, wd


# ----------------------------------------------------------------------
# Dense baseline (the "NCCL AllReduce" arm of the paper's evaluation)
# ----------------------------------------------------------------------

def dense_all_reduce(grads: Any, axis_names: Sequence[str],
                     acc_dtype=jnp.float32, mean: bool = True) -> Any:
    """Plain psum of raw gradients over the DP axes."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    w = 1
    for ax in axis_names:
        w *= jax.lax.axis_size(ax)

    def red(g):
        s = jax.lax.psum(g.astype(acc_dtype), tuple(axis_names))
        if mean:
            s = s / w
        return s.astype(g.dtype)

    return jax.tree.map(red, grads)


# ----------------------------------------------------------------------
# Error-feedback state + the compatibility wrapper
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AggregationState:
    """Per-leaf error-feedback residuals (empty pytree when disabled).

    Residuals keep the parameter pytree layout; the bucketed aggregators
    expose per-bucket views of them via ``BucketPlan.residual_slices``.

    ``telemetry`` (PR 6): measured per-bucket signals the ``auto``
    wire-plan controller folds into its cost model — currently a dict
    with ``bucket_occupancy`` (per-bucket nonzero fraction of the
    aggregated stream, identical on every rank). ``None`` for the fixed
    strategies, whose jaxprs stay telemetry-free; the train step
    surfaces it through the metrics dict, it is never carried across
    steps.
    """
    residual: Any
    telemetry: Any = None


def init_aggregation_state(params: Any, cfg: CompressionConfig) -> AggregationState:
    """Residuals live with the parameters (same shape & sharding)."""
    if cfg.topk_ratio is not None and cfg.error_feedback:
        res = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    else:
        res = jax.tree.map(lambda p: jnp.zeros((0,), jnp.float32), params)
    return AggregationState(residual=res)


def compressed_all_reduce(grads: Any, agg_state: AggregationState,
                          param_specs: Any, mesh,
                          cfg: CompressionConfig,
                          dp_axes: Sequence[str] = ("data",),
                          tp_axes: Sequence[str] = ("model",),
                          mean: bool = True,
                          reduce_scatter: bool = False,
                          outer_manual: Optional[Sequence[str]] = None):
    """Aggregate a gradient pytree with the paper's compressed pipeline.

    Thin wrapper over the bucketed
    :class:`~repro.core.aggregators.CompressedAggregator` (or the
    reduce-scatter variant), kept for API compatibility with the
    pre-bucketing per-leaf path. Must be called *inside* a ``shard_map``
    where ``dp_axes`` are already manual.

    ``outer_manual``: the axis set that enclosing shard_map takes manual
    — forwarded to the aggregator, where a full-manual caller lets the
    reduce-scatter strategy reassemble with a manual-axis all_gather.
    Omitting it never affects correctness.

    Returns: (aggregated grads pytree, new AggregationState)
    """
    # Imported here: aggregators imports this module's primitives.
    from .aggregators import make_aggregator
    name = "compressed_rs" if reduce_scatter else "compressed"
    agg = make_aggregator(name, cfg, mesh, dp_axes=dp_axes,
                          tp_axes=tp_axes, mean=mean,
                          outer_manual=outer_manual)
    return agg(grads, agg_state, param_specs)
