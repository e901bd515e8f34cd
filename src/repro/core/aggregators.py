"""Aggregator strategies: bucketed gradient aggregation (PR 2).

The pre-bucketing pipeline unrolled a Python loop over every pytree leaf —
each leaf got its own codec plan, its own nested ``shard_map`` regions and
its own ``psum`` + OR-AllReduce launch, so a 100-leaf model compiled ~100
copies of the codec and paid ~100x collective launch latency. Here the
whole gradient is packed into fixed-byte flat buckets
(:mod:`repro.core.bucketing`) and aggregation is a pluggable strategy:

- :class:`DenseAggregator`              — plain ``psum`` (the paper's NCCL
  baseline arm);
- :class:`CompressedAggregator`         — ONE sketch encode over the packed
  stream, ONE stacked sketch-``psum`` and ONE OR-AllReduce for *all*
  buckets. With ``cfg.overlap`` / ``cfg.stream_chunks`` the wire is cut
  into whole-bucket chunks and driven through the shared
  :func:`repro.core.streams.stream_schedule` double-buffer pipeline, so
  on hardware with async collectives chunk *i*'s wire time hides chunk
  *i+1*'s encode;
- :class:`CompressedReduceScatterAggregator` — the native reduce-scatter
  wire path (PR 3): the sketch reduces with ``jax.lax.psum_scatter`` and
  the bitmap with the ppermute-ring
  :func:`~repro.core.collectives.or_reduce_scatter`, so each rank
  *receives* only its own ``n_buckets/W`` sketch+bitmap slice (1/W the
  reduced payload of the AllReduce strategies — the paper's full
  reduce-scatter bandwidth win), peels only that range (1/W of the
  recovery compute), and reassembles the recovered chunks with a
  manual-axis ``all_gather`` (full-manual regions) or the zero-pad +
  ``psum`` ZeRO-1 gather trick (partial-auto, where Shardy would
  un-shard auto TP axes around the gather). The older ``psum`` +
  local-slice emulation (AllReduce wire, per-rank peel compute only)
  stays selectable with ``cfg.rs_wire="emulate"``. Overlap is honored on the
  native wire too: the stream scheduler stages per-chunk
  ``psum_scatter``/OR-Reduce-Scatter calls over chunks of whole
  per-rank bucket runs, and when the chunk grid aligns with the ZeRO-1
  optimizer slices (``zero1_dims``) the per-rank recovered chunks feed
  the optimizer shards directly and the recovered-chunk all_gather is
  skipped entirely.
- :class:`CompressedInNetworkAggregator` — the in-network tier (PR 4):
  the stream goes up an emulated worker->ToR->spine switch tree
  (:mod:`repro.net`) once per worker — integer-add sketch (via the
  fixed-point wire when ``cfg.wire_dtype='fxp32'``) and OR bitmap —
  instead of around a ring, so the hottest (root) link carries ``1 x``
  the payload per direction vs the ring's ``2(W-1)/W x``.

Plan/execute split (PR 6): every compressed strategy is now a per-group
*executor* behind a :class:`~repro.core.wireplan.WirePlan`.  A fixed
strategy executes the degenerate uniform plan (one group, its own wire —
byte-for-byte today's jaxprs), while a non-trivial ``wire_plan`` splits
the bucket stream into contiguous groups and runs each group through the
assigned wire's executor at its global block offsets
(``StreamPlan.base_block``), so any mixed plan is bit-for-bit the fixed
strategies it composes on the buckets it assigns.  The 5th registry
entry ``auto`` (:class:`WirePlannedAggregator`) executes plans produced
by the :mod:`repro.core.costmodel` controller and measures the per-bucket
occupancy telemetry the controller feeds on.

All strategies run *inside* the outer train-step ``shard_map`` (manual DP
axes). Packing/unpacking runs in a nested ``shard_map`` that takes the
tensor-parallel axes manual too, so each device packs only its local
parameter shards — no GSPMD resharding of gradients — while the codec and
the DP collectives run at the outer level on the shard-local buckets.

Sparsification / error feedback are applied **per leaf** inside the pack
stage — identical semantics (and bits) to the per-leaf path this replaced,
pinned by ``tests/drivers/collectives_driver.py`` — and residuals keep the
parameter pytree layout. :meth:`BucketPlan.residual_slices` exposes the
per-bucket view of those residuals.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Protocol, Sequence, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import functools

from repro import compat
from repro.net.fixedpoint import FixedPointWire
from repro.net.topology import make_topology, tree_all_reduce
from .config import CompressionConfig
from .compressor import HomomorphicCompressor, CompressedLeaf
from .bucketing import BucketPlan, make_bucket_plan, make_dest_bucket_plans
from .collectives import (AggregationState, alltoall_lane_sum,
                          dense_all_reduce, gather_chunk_slices, linear_rank,
                          or_allreduce, or_reduce_scatter, sketch_all_to_all)
from .streams import (StreamPlan, make_alltoall_stream_plan, make_stream_plan,
                      stream_schedule, zero1_gather_skip)
from .wireplan import WIRES, WirePlan, pattern_wires, uniform_plan
from . import topk as topk_lib


@runtime_checkable
class Aggregator(Protocol):
    """Strategy for aggregating a gradient pytree across the DP axes.

    Called inside a ``shard_map`` where the DP axes are manual. Returns
    the aggregated (mean) gradients and the new error-feedback state.
    """

    def __call__(self, grads: Any, state: AggregationState,
                 param_specs: Any) -> Tuple[Any, AggregationState]:
        ...


# ----------------------------------------------------------------------
# Dense (the NCCL-AllReduce baseline arm)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseAggregator:
    """Same constructor surface as the compressed strategies so the
    registry can build any entry uniformly; cfg/tp_axes/outer_manual are
    simply unused here."""

    wire = "dense"  # the WirePlan wire this strategy is the executor for

    mesh: Any
    dp_axes: Tuple[str, ...]
    cfg: Any = None
    tp_axes: Tuple[str, ...] = ()
    mean: bool = True
    outer_manual: Any = None
    zero1_dims: Any = None
    wire_plan: Any = None  # ctor uniformity only: dense groups of a
                           # mixed plan run inline in the compressed
                           # executors (a psum needs no codec plumbing)

    def __call__(self, grads, state: AggregationState, param_specs=None):
        if self.wire_plan is not None:
            raise ValueError(
                "DenseAggregator does not execute wire plans; use the "
                "'auto' strategy (or a compressed strategy with "
                "wire_plan=...) for per-bucket-group wires")
        return dense_all_reduce(grads, self.dp_axes, mean=self.mean), state


# ----------------------------------------------------------------------
# Shared machinery for the compressed strategies
# ----------------------------------------------------------------------

def _tp_only(spec, dp_set):
    """Strip DP-axis references from a PartitionSpec (those axes are
    manual in the outer shard_map; nested regions partition TP only)."""
    if spec is None:
        return P()
    parts = []
    for s in spec:
        if s is None:
            parts.append(None)
        elif isinstance(s, (tuple, list)):
            kept = tuple(a for a in s if a not in dp_set)
            parts.append(kept if kept else None)
        else:
            parts.append(None if s in dp_set else s)
    return P(*parts)


def _spec_axes(spec) -> set:
    out = set()
    for part in spec:
        if part is None:
            continue
        out |= set(part) if isinstance(part, (tuple, list)) else {part}
    return out


def _local_shape(shape, spec, mesh):
    """Per-device shape of a leaf sharded as ``spec`` on ``mesh``."""
    def div(i):
        part = spec[i] if i < len(spec) else None
        if part is None:
            return 1
        names = part if isinstance(part, (tuple, list)) else (part,)
        d = 1
        for nm in names:
            d *= mesh.shape[nm]
        return d
    return tuple(sz // div(i) for i, sz in enumerate(shape))


def sparsify_leaf(flat: jnp.ndarray, res: jnp.ndarray,
                  cfg: CompressionConfig):
    """Per-leaf phase-0: top-k budget + error feedback on one flat leaf.

    Identical math to the per-leaf path this layer replaced (pinned
    bit-for-bit by the collectives driver): k is proportional to *this
    leaf's* (shard-local) element count. Public because the elastic
    client (``repro.elastic.client``) must sparsify with exactly these
    semantics for its folds to be bit-identical to the in-mesh
    strategies.
    """
    new_res = res
    if cfg.topk_ratio is not None:
        k = max(1, int(flat.shape[0] * cfg.topk_ratio))
        if cfg.error_feedback:
            flat, new_res = topk_lib.apply_error_feedback(
                flat, res.reshape(-1), k, exact=cfg.topk_exact)
        elif cfg.topk_exact:
            flat = topk_lib.sparsify_topk(flat, k)
        else:
            flat = topk_lib.sparsify_threshold(flat, k)
    return flat, new_res


def pack_stream(plan: BucketPlan, g_tree, r_tree, cfg: CompressionConfig):
    """The compressed strategies' pack stage: per-leaf sparsify/EF
    (:func:`sparsify_leaf`), then bucket-pack. Returns the
    ``(n_buckets, bucket_elems)`` f32 stream and the new residual tree."""
    g_leaves = plan.treedef.flatten_up_to(g_tree)
    r_leaves = plan.treedef.flatten_up_to(r_tree)
    flats, new_res = [], []
    for g, r in zip(g_leaves, r_leaves):
        flat, nr = sparsify_leaf(g.reshape(-1).astype(jnp.float32), r, cfg)
        flats.append(flat)
        new_res.append(nr.reshape(r.shape))
    return plan.pack_flat(flats), jax.tree.unflatten(plan.treedef, new_res)


@dataclasses.dataclass(frozen=True)
class CompressedAggregator:
    """The paper's pipeline over one fused bucket stream.

    pack (shard-local) -> per-leaf sparsify/EF -> encode all buckets ->
    sketch psum + index OR-AllReduce -> peel -> unpack.
    """

    wire = "compressed"        # the WirePlan wire this class executes
    collect_telemetry = False  # WirePlannedAggregator flips this

    cfg: CompressionConfig
    mesh: Any
    dp_axes: Tuple[str, ...]
    tp_axes: Tuple[str, ...] = ("model",)
    mean: bool = True
    # The axis set the *caller's* shard_map takes manual. A full-manual
    # caller lets the reduce-scatter variant reassemble with a
    # manual-axis all_gather (see _gather_chunks).
    outer_manual: Any = None
    # Per-leaf ZeRO-1 slice dims (from streams.zero_slice_dim, in
    # flattened-leaf order; None entries = unsliced leaves). Only the
    # reduce-scatter variant consults it — when the stream chunk grid
    # aligns with these slices, its recovered-chunk all_gather is
    # skipped and each rank feeds its optimizer shard directly.
    zero1_dims: Any = None
    # Explicit per-bucket-group wire assignment (PR 6). None = the
    # degenerate uniform plan on this strategy's own wire, i.e. exactly
    # the pre-PR-6 behaviour (same jaxprs). A non-trivial WirePlan runs
    # each group through the assigned wire's executor; see
    # :meth:`_execute_plan`.
    wire_plan: Any = None
    # Global hash-plan block id of this executor's first bucket —
    # nonzero only on group delegates, so a group's encode/peel hash
    # exactly like the corresponding slice of the full-stream pass.
    base_block: int = 0

    # -- construction helpers ------------------------------------------

    def _n_workers(self) -> int:
        if not self.mean:
            return 1
        return self._dp_world()

    def _dp_world(self) -> int:
        W = 1
        for ax in self.dp_axes:
            W *= self.mesh.shape[ax]
        return W

    def _full_manual(self) -> bool:
        return (self.outer_manual is not None
                and compat.full_manual_region(self.outer_manual, self.mesh))

    def _manual_set(self, spec_leaves) -> set:
        """Axes the nested pack/unpack regions must take manual: the TP
        axes plus every axis any leaf's (DP-stripped) spec references
        (e.g. expert-parallel axes)."""
        manual = {a for a in self.tp_axes if a and a in self.mesh.shape}
        for spec in spec_leaves:
            manual |= _spec_axes(spec)
        return manual

    # -- phase I/II bucket codec (runs on shard-local buckets) ---------

    def _stream_plan(self, plan: BucketPlan) -> StreamPlan:
        """The wire-chunk grid for this strategy (subclasses align it to
        their wire's boundaries — per-rank RS chunks, switch windows)."""
        return make_stream_plan(plan, self.cfg, base_block=self.base_block)

    def _reduce_allreduce(self, dp_idx):
        """The AllReduce wire for one (sketch, words) payload chunk."""
        @jax.named_scope("reduce")
        def red(payload):
            sk, words = payload
            return (jax.lax.psum(sk, tuple(self.dp_axes)),
                    or_allreduce(words, self.dp_axes, axis_indices=dp_idx))
        return red

    def _encode_streamed(self, buckets, splan: StreamPlan,
                         comp: HomomorphicCompressor, reduce_fn,
                         with_maxabs: bool = False):
        """Per-chunk encode + wire through the shared scheduler.

        Each chunk makes ONE producer-op pass over its gradient slice
        (`HomomorphicCompressor.compress_wire` — fused sketch + packed
        bitmap + per-block maxabs on fused-capable geometries) and hands
        the payload to ``reduce_fn`` for the collectives. Returns the
        reduced per-chunk payloads stacked on a leading ``n_chunks`` dim
        (whatever shapes ``reduce_fn`` emits). Bit-identical to the
        one-shot path: each chunk encodes under the stream's global hash
        plan via ``block_offset``, the bitmap slices exactly per bucket,
        and padding buckets are zeros end to end.

        ``with_maxabs``: include the per-block max magnitudes in the
        per-chunk payload (the fxp32 wire's exponent ingredient — free
        on the fused path, where the producer kernel emits it anyway).
        """
        @jax.named_scope("encode")
        def enc(i, chunk):
            leaf, mx = comp.compress_wire(
                chunk.reshape(-1),
                block_offset=splan.chunk_start_block(i))
            if with_maxabs:
                return leaf.sketch, leaf.index_words, mx
            return leaf.sketch, leaf.index_words

        return stream_schedule(splan.chunk_view(buckets), enc, reduce_fn)

    def _trim_fused(self, stacked_sk, stacked_words, plan: BucketPlan,
                    splan: StreamPlan):
        """Stacked per-chunk (sketch, words) -> fused full-stream views,
        padding chunks dropped."""
        cfg = self.cfg
        sk = stacked_sk.reshape(-1, cfg.rows, cfg.lanes)
        words = stacked_words.reshape(-1)
        return (sk[:plan.n_buckets * splan.blocks_per_bucket],
                words[:plan.n_buckets * splan.words_per_bucket])

    def _encode(self, buckets: jnp.ndarray, plan: BucketPlan,
                comp: HomomorphicCompressor, dp_idx):
        """(n_buckets, E) local buckets -> aggregated wire payload.

        The wire-contract half of PR 7: every strategy's ``_encode``
        makes ONE producer-op pass over the bucket stream (fused
        sketch + pack (+ maxabs) via ``compress``/``compress_wire``)
        before its collectives, and returns a payload tuple its own
        ``_recover`` consumes in ONE consumer-op pass after them. This
        class's payload is ``(sketch, words)``; subclasses may extend it
        (the fxp32 tree adds the shared exponents)."""
        splan = self._stream_plan(plan)
        if not splan.streamed:
            with jax.named_scope("encode"):
                c = comp.compress(buckets.reshape(-1),
                                  block_offset=self.base_block)
            return self._reduce_allreduce(dp_idx)((c.sketch, c.index_words))
        sks, ws = self._encode_streamed(buckets, splan, comp,
                                        self._reduce_allreduce(dp_idx))
        return self._trim_fused(sks, ws, plan, splan)

    def _recover(self, payload, plan: BucketPlan,
                 comp: HomomorphicCompressor, dp_idx, dp_rank,
                 spec_leaves=None):
        """Aggregated wire payload -> recovered (n_buckets, E), in ONE
        consumer-op pass (fused unpack + peel via ``recover``).

        ``spec_leaves``: the leaves' DP-stripped PartitionSpecs — only
        the reduce-scatter subclass consults them (the gather-skip path
        must know whether the packed stream is a TP-local view)."""
        sk, words = payload
        with jax.named_scope("peel"):
            rec = comp.recover(CompressedLeaf(sketch=sk, index_words=words),
                               plan.padded, block_offset=self.base_block)
        return rec.reshape(plan.n_buckets, plan.bucket_elems)

    # -- plan / execute (PR 6) -----------------------------------------

    def _wire_plan(self, plan: BucketPlan) -> WirePlan:
        """The WirePlan this pass executes: the explicit one when set,
        else the degenerate uniform plan on this strategy's own wire."""
        if self.wire_plan is not None:
            if self.wire_plan.n_buckets != plan.n_buckets:
                raise ValueError(
                    f"wire_plan covers {self.wire_plan.n_buckets} "
                    f"buckets, stream has {plan.n_buckets}")
            return self.wire_plan
        return uniform_plan(plan.n_buckets, self.wire)

    def _group_delegate(self, group, base_block: int):
        """The executor instance for one wire group: the group wire's
        registry class, offset to the group's global block position.
        Group delegates never gather-skip (``zero1_dims=None``): the
        ZeRO-1 alignment math is defined on the full stream."""
        cfg = self.cfg if group.stream_chunks is None else \
            dataclasses.replace(self.cfg, stream_chunks=group.stream_chunks)
        return AGGREGATORS[group.wire](
            cfg=cfg, mesh=self.mesh, dp_axes=self.dp_axes,
            tp_axes=self.tp_axes, mean=self.mean,
            outer_manual=self.outer_manual, zero1_dims=None,
            base_block=base_block)

    def _run_group(self, buckets, plan: BucketPlan,
                   comp: HomomorphicCompressor, dp_idx, dp_rank):
        """Execute one group's encode -> wire -> recover on this
        executor's own wire (``plan`` is the group view; ``buckets`` its
        row slice of the packed stream)."""
        payload = self._encode(buckets, plan, comp, dp_idx)
        return self._recover(payload, plan, comp, dp_idx, dp_rank)

    def _execute_plan(self, buckets, plan: BucketPlan,
                      comp: HomomorphicCompressor, dp_idx, dp_rank,
                      spec_leaves=None):
        """(n_buckets, E) local buckets -> aggregated (n_buckets, E).

        The trivial uniform plan on this strategy's own wire takes the
        exact pre-PR-6 path over the original BucketPlan (same jaxprs —
        gather-skip and ZeRO-1 plumbing intact). Otherwise each group
        runs through its wire's executor at its global block offsets:
        dense groups are a plain ``psum`` of the packed f32 stream (the
        mean lands at unpack with everyone else's), compressed groups
        re-dispatch through the registry. Per-leaf sparsify/EF already
        happened at pack, so every group is bit-for-bit the fixed
        strategy it names on the buckets it covers (dense groups match
        the compressed wires bitwise in the lossless regime, where
        recovery is exact).
        """
        wplan = self._wire_plan(plan)
        if wplan.is_trivial and wplan.groups[0].wire == self.wire:
            payload = self._encode(buckets, plan, comp, dp_idx)
            return self._recover(payload, plan, comp, dp_idx, dp_rank,
                                 spec_leaves=spec_leaves)
        nbpb = plan.blocks_per_bucket(self.cfg)
        parts = []
        for g in wplan.groups:
            bgroup = buckets[g.start:g.stop]
            if g.wire == "dense":
                with jax.named_scope("reduce"):
                    parts.append(jax.lax.psum(bgroup, tuple(self.dp_axes)))
                continue
            gview = plan.group_view(g.start, g.n_buckets)
            delegate = self._group_delegate(g, base_block=g.start * nbpb)
            parts.append(delegate._run_group(
                bgroup, gview, HomomorphicCompressor(delegate.cfg),
                dp_idx, dp_rank))
        return jnp.concatenate(parts, axis=0)

    # -- the strategy --------------------------------------------------

    def __call__(self, grads, state: AggregationState, param_specs):
        cfg = self.cfg
        comp = HomomorphicCompressor(cfg)
        mesh = self.mesh
        dp_set = set(self.dp_axes)
        n_workers = self._n_workers()
        ef_on = cfg.topk_ratio is not None and cfg.error_feedback

        leaves, treedef = jax.tree.flatten(grads)
        spec_leaves = [_tp_only(s, dp_set)
                       for s in treedef.flatten_up_to(param_specs)]
        res_tree = state.residual
        res_specs = jax.tree.unflatten(
            treedef, [s if ef_on else P() for s in spec_leaves])
        specs = jax.tree.unflatten(treedef, spec_leaves)

        # Shard indices on the (outer-manual) DP axes, computed here where
        # those axes are directly bound; threaded into the OR-rings because
        # axis_index inside nested regions would re-bind the axis (Shardy).
        dp_idx = {ax: jax.lax.axis_index(ax) for ax in self.dp_axes}
        dp_rank = linear_rank(self.dp_axes, dp_idx)

        manual = self._manual_set(spec_leaves)
        nested = bool(manual)
        if nested:
            local_shapes = [
                _local_shape(g.shape, s, mesh)
                for g, s in zip(leaves, spec_leaves)]
        else:
            # Pure DP: the global view is the local one.
            local_shapes = [tuple(g.shape) for g in leaves]
        plan = make_bucket_plan(
            grads, cfg, shapes=jax.tree.unflatten(treedef, local_shapes))

        @jax.named_scope("pack")
        def pack_stage(g_tree, r_tree):
            """Shard-local: per-leaf sparsify/EF, then bucket-pack."""
            return pack_stream(plan, g_tree, r_tree, cfg)

        @jax.named_scope("unpack")
        def unpack_stage(buckets):
            """Shard-local: bucket stream -> leaf pytree (mean)."""
            return plan.unpack(buckets / n_workers)

        if nested:
            enc = compat.shard_map(
                pack_stage, mesh=mesh, in_specs=(specs, res_specs),
                out_specs=(P(), res_specs), axis_names=manual,
                check_vma=False)
            buckets, new_res = enc(grads, res_tree)
        else:
            buckets, new_res = pack_stage(grads, res_tree)

        rec = self._execute_plan(buckets, plan, comp, dp_idx, dp_rank,
                                 spec_leaves=spec_leaves)

        if nested:
            dec = compat.shard_map(
                unpack_stage, mesh=mesh, in_specs=(P(),),
                out_specs=specs, axis_names=manual, check_vma=False)
            agg = dec(rec)
        else:
            agg = unpack_stage(rec)
        telemetry = None
        if self.collect_telemetry:
            # Per-bucket nonzero fraction of the aggregated stream —
            # identical on every rank (the recovered stream is), so the
            # train step may psum/average it freely. The controller
            # compares it against the peeling capacity to rule the
            # compressed wires in or out per bucket.
            telemetry = {"bucket_occupancy": jnp.mean(
                (rec != 0).astype(jnp.float32), axis=1)}
        return agg, AggregationState(residual=new_res, telemetry=telemetry)


@dataclasses.dataclass(frozen=True)
class CompressedReduceScatterAggregator(CompressedAggregator):
    """Bucketed compressed aggregation over a reduce-scattered wire.

    Phase I (pack/sparsify/encode) is identical to
    :class:`CompressedAggregator`. Phase II comes in two wire paths,
    selected by ``cfg.rs_wire``:

    **Native** (the default): the stacked sketch reduces with
    ``jax.lax.psum_scatter`` and the bitmap with the ring
    :func:`~repro.core.collectives.or_reduce_scatter`, both padded to
    whole per-rank chunks of ``nb_p/W`` buckets, so each rank *receives*
    only its own sketch+bitmap slice — 1/W the reduced payload (and
    roughly half the link traffic) of the AllReduce strategies. The rank
    peels its range (1/W of the recovery compute, hash ids offset to the
    chunk's global block position) and the recovered chunks reassemble
    with a manual-axis ``all_gather`` in full-manual regions, else the
    zero-pad + ``psum`` ZeRO-1 gather trick (Shardy un-shards auto TP
    axes around a partial-auto manual-axis all_gather; see
    train/step.py).

    ``cfg.overlap`` / ``cfg.stream_chunks`` are honored on the native
    wire (PR 5): the shared stream scheduler cuts the payload into
    chunks of whole *per-rank bucket runs* (``chunk_buckets = k * W``,
    so every per-chunk ``psum_scatter`` / OR-Reduce-Scatter lands whole
    buckets on their peeling rank — the chunk count must divide
    ``ceil(n_buckets/W)``, ValueError otherwise), pipelines chunk
    ``i``'s scatter against chunk ``i+1``'s encode, and peels each
    received slice at its global block offset. Reassembly restores the
    exact one-shot stream
    (:func:`~repro.core.collectives.gather_chunk_slices`) — unless the
    chunk grid aligns with the ZeRO-1 optimizer slices (``zero1_dims``;
    :func:`repro.core.streams.zero1_gather_skip`), in which case each
    rank already holds every gradient value its optimizer shard
    consumes, the recovered-chunk all_gather is skipped, and the
    returned leaves are exact inside this rank's owned coordinates and
    zero outside (the train step reduces the grad-norm across ranks on
    that path; ``strategy_wire_bytes`` shows the saved gather wire).

    **Emulated** (``rs_wire="emulate"``): full ``psum`` + OR-AllReduce,
    then a local slice — AllReduce wire cost, but still only 1/W of the
    peel compute per rank. Overlap on this wire is plain AllReduce
    chunking (the base class schedule).

    All paths are bit-identical to :class:`CompressedAggregator` (modulo
    the gather-skip output contract above): the per-range peel runs the
    same ops on the same sketch slice, and the disjoint-chunk gather
    (all_gather, or psum onto zeros) reproduces each value exactly once.
    """

    wire = "compressed_rs"

    # -- geometry / capability helpers ---------------------------------

    def _native_wire(self) -> bool:
        """Whether phase II takes the psum_scatter/OR-RS wire path (the
        one predicate :meth:`_stream_plan` also reads, so the chunk grid
        can never drift from the wire path taken)."""
        return self.cfg.rs_wire != "emulate"

    def _check_bitmap(self):
        if self.cfg.index != "bitmap":
            raise ValueError(
                "compressed_rs requires index='bitmap' (a Bloom filter "
                "hashes global coordinates and cannot be sliced per-rank)")

    def _rs_geometry(self, plan: BucketPlan):
        """(W, blocks/bucket, words/bucket, n_buckets padded to W)."""
        W = self._dp_world()
        nbpb = plan.blocks_per_bucket(self.cfg)
        wpb = plan.words_per_bucket
        nb_p = -(-plan.n_buckets // W) * W
        return W, nbpb, wpb, nb_p

    def _stream_plan(self, plan: BucketPlan) -> StreamPlan:
        """Per-rank-aligned scatter grid on the native wire (chunks of
        whole per-rank bucket runs); the base AllReduce grid elsewhere
        (the emulated wire ships the whole stream anyway, and a 1-rank
        'scatter' is a no-op)."""
        if self._native_wire() and self._dp_world() > 1:
            return make_stream_plan(plan, self.cfg,
                                    workers=self._dp_world(), scatter=True,
                                    base_block=self.base_block)
        return super()._stream_plan(plan)

    def _gather_skip(self, plan: BucketPlan, splan: StreamPlan,
                     spec_leaves=None) -> bool:
        """Static: does the chunk grid align with the ZeRO-1 slices so
        the recovered-chunk all_gather can be skipped?

        ``spec_leaves`` (DP-stripped specs): a leaf actually sharded on a
        non-DP axis makes the packed stream a TP-*local* view while the
        ZeRO-1 slices are global — the alignment math does not apply,
        keep the gather."""
        if self.zero1_dims is None:
            return False
        if spec_leaves is not None \
                and any(_spec_axes(s) for s in spec_leaves):
            return False
        return zero1_gather_skip(splan, plan, tuple(self.zero1_dims))

    def gather_skip_active(self, grads, param_specs=None) -> bool:
        """Static answer (no tracing): will aggregating gradients shaped
        like ``grads`` (sharded as ``param_specs``; None = replicated)
        skip the recovered-chunk all_gather? The train step consults
        this to switch the grad-norm to a cross-rank reduction on the
        skip path; tests pin it against the wire accounting
        (``strategy_wire_bytes(..., zero1_aligned=...)``)."""
        if not (self._native_wire() and self._dp_world() > 1):
            return False
        plan = make_bucket_plan(grads, self.cfg)
        splan = self._stream_plan(plan)
        spec_leaves = None
        if param_specs is not None:
            dp_set = set(self.dp_axes)
            spec_leaves = [_tp_only(s, dp_set) for s in
                           plan.treedef.flatten_up_to(param_specs)]
        return splan.streamed and self._gather_skip(plan, splan,
                                                    spec_leaves)

    # -- phase II ------------------------------------------------------

    def _encode(self, buckets: jnp.ndarray, plan: BucketPlan,
                comp: HomomorphicCompressor, dp_idx):
        self._check_bitmap()
        if not self._native_wire() or self._dp_world() == 1:
            if self._native_wire() and not self._stream_plan(plan).streamed:
                # 1-rank native wire: nothing to scatter or reduce.
                with jax.named_scope("encode"):
                    c = comp.compress(buckets.reshape(-1),
                                      block_offset=self.base_block)
                return c.sketch, c.index_words
            return super()._encode(buckets, plan, comp, dp_idx)
        splan = self._stream_plan(plan)
        if splan.streamed:
            return self._encode_streamed(buckets, splan, comp,
                                         self._reduce_scatter(dp_idx))
        # One-shot native wire: a single psum_scatter + OR-RS over the
        # whole stream, padded to whole per-rank chunks.
        W, nbpb, wpb, nb_p = self._rs_geometry(plan)
        with jax.named_scope("encode"):
            c = comp.compress(buckets.reshape(-1),
                              block_offset=self.base_block)
            sk, words = c.sketch, c.index_words
            pad_b = nb_p - plan.n_buckets
            if pad_b:
                # zero sketch blocks / zero index words peel to exact zeros
                sk = jnp.pad(sk, ((0, pad_b * nbpb), (0, 0), (0, 0)))
                words = jnp.pad(words, (0, pad_b * wpb))
        return self._reduce_scatter(dp_idx)((sk, words))

    def _reduce_scatter(self, dp_idx):
        """The native wire for one (sketch, words) payload chunk: each
        rank receives its own fully-reduced whole-bucket slice."""
        @jax.named_scope("reduce")
        def red(payload):
            sk, words = payload
            sk_loc = jax.lax.psum_scatter(
                sk, tuple(self.dp_axes), scatter_dimension=0, tiled=True)
            w_loc = or_reduce_scatter(words, self.dp_axes,
                                      axis_indices=dp_idx)
            return sk_loc, w_loc
        return red

    def _recover(self, payload, plan: BucketPlan,
                 comp: HomomorphicCompressor, dp_idx, dp_rank,
                 spec_leaves=None):
        cfg = self.cfg
        sk, words = payload
        self._check_bitmap()
        W, nbpb, wpb, nb_p = self._rs_geometry(plan)
        chunk_b = nb_p // W                      # buckets per rank
        chunk_elems = chunk_b * plan.bucket_elems
        if self._native_wire():
            splan = self._stream_plan(plan)
            if W > 1 and splan.streamed:
                return self._recover_streamed(sk, words, plan, splan, comp,
                                              dp_idx, dp_rank, spec_leaves)
            # (sk, words) are already this rank's reduced 1/W slice (the
            # whole stream at W == 1).
            with jax.named_scope("peel"):
                rec_loc = comp.recover(
                    CompressedLeaf(sketch=sk, index_words=words), chunk_elems,
                    block_offset=self.base_block + dp_rank * chunk_b * nbpb)
            return self._gather_chunks(rec_loc, plan, nb_p, chunk_elems,
                                       dp_rank)
        with jax.named_scope("peel"):
            pad_b = nb_p - plan.n_buckets
            if pad_b:
                sk = jnp.pad(sk, ((0, pad_b * nbpb), (0, 0), (0, 0)))
                words = jnp.pad(words, (0, pad_b * wpb))
            sk_loc = jax.lax.dynamic_slice_in_dim(
                sk, dp_rank * chunk_b * nbpb, chunk_b * nbpb, axis=0)
            w_loc = jax.lax.dynamic_slice_in_dim(
                words, dp_rank * chunk_b * wpb, chunk_b * wpb, axis=0)
            rec_loc = comp.recover(
                CompressedLeaf(sketch=sk_loc, index_words=w_loc), chunk_elems,
                block_offset=self.base_block + dp_rank * chunk_b * nbpb)
        return self._gather_chunks(rec_loc, plan, nb_p, chunk_elems, dp_rank)

    def _recover_streamed(self, sk, words, plan: BucketPlan,
                          splan: StreamPlan, comp: HomomorphicCompressor,
                          dp_idx, dp_rank, spec_leaves=None):
        """Streamed native wire: ``(sk, words)`` are the per-chunk
        reduced slices stacked on a leading ``n_chunks`` dim — peel each
        at its global block offset (still 1/W of the recovery compute),
        then reassemble (or skip the gather when the chunk grid aligns
        with the ZeRO-1 slices: each rank keeps its recovered values in
        place in a zero stream — exact inside its owned coordinates)."""
        slice_elems = splan.rank_chunk_buckets * plan.bucket_elems

        def peel(args):
            j, sk_j, w_j = args
            return comp.recover(
                CompressedLeaf(sketch=sk_j, index_words=w_j), slice_elems,
                block_offset=splan.rank_slice_start_block(j, dp_rank))

        idx = jnp.arange(splan.n_chunks, dtype=jnp.int32)
        with jax.named_scope("peel"):
            rec = jax.lax.map(peel, (idx, sk, words))  # (n_chunks, slice_elems)
        with jax.named_scope("unpack"):
            if self._gather_skip(plan, splan, spec_leaves):
                full = jnp.zeros((splan.n_chunks, splan.chunk_elems),
                                 rec.dtype)
                full = jax.lax.dynamic_update_slice(
                    full, rec, (jnp.int32(0), dp_rank * slice_elems))
            else:
                # Same gate as _gather_chunks: the manual-axis all_gather
                # only in full-manual regions — partial-auto keeps the
                # zero-pad + psum trick so Shardy does not un-shard the
                # auto TP axes around the gather.
                full = gather_chunk_slices(
                    rec, tuple(self.dp_axes), axis_indices=dp_idx,
                    use_all_gather=self._full_manual())
            stream = full.reshape(-1)[:plan.padded]
            return stream.reshape(plan.n_buckets, plan.bucket_elems)

    @jax.named_scope("unpack")
    def _gather_chunks(self, rec_loc, plan: BucketPlan, nb_p: int,
                       chunk_elems: int, dp_rank):
        """Reassemble the per-rank recovered chunks into the full stream.

        Full-manual regions take a manual-axis ``all_gather`` (rank-major
        tiling, half the wire of the psum trick); partial-auto regions
        keep the zero-pad + ``psum`` gather so Shardy does not un-shard
        the auto TP axes around the gather (see train/step.py). Both
        reproduce each recovered value exactly once (bit-identical).
        """
        if self._dp_world() == 1:
            full = rec_loc
        elif self._full_manual():
            full = jax.lax.all_gather(rec_loc, tuple(self.dp_axes),
                                      axis=0, tiled=True)
        else:
            full = jnp.zeros((nb_p * plan.bucket_elems,), rec_loc.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(
                full, rec_loc, dp_rank * chunk_elems, axis=0)
            full = jax.lax.psum(full, tuple(self.dp_axes))
        return full[:plan.padded].reshape(plan.n_buckets, plan.bucket_elems)


@dataclasses.dataclass(frozen=True)
class CompressedInNetworkAggregator(CompressedAggregator):
    """Bucketed compressed aggregation through an emulated in-network
    tier (PR 4): the paper's "aggregate inside the switch" deployment.

    Phase I (pack/sparsify/encode) is :class:`CompressedAggregator`'s.
    Phase II ships the stream up a worker -> ToR -> spine reduction tree
    (:mod:`repro.net.topology`, mapped onto the DP mesh axes by
    ``cfg.topology``) instead of a ring, in one of two wire dtypes:

    - ``cfg.wire_dtype == "fxp32"`` — the honest switch wire: the
      sketch is quantized per bucket to shared-exponent int32
      (:class:`repro.net.fixedpoint.FixedPointWire`, overflow-free for
      this DP world size by construction), the per-bucket exponents are
      agreed with a ``pmax`` (4 bytes/bucket of metadata), and both the
      integer sketch and the uint32 bitmap ride
      :func:`repro.net.topology.tree_all_reduce` — integer add + OR,
      the only operations a programmable data plane has. Because
      integer adds are exact in any association order, the result is
      bit-identical to the documented codec roundtrip (and to a flat
      ``psum``).
    - ``cfg.wire_dtype == "f32"`` — an idealized float-capable
      aggregation tier (e.g. host-based aggregation servers): reuses
      the sketch-``psum`` + OR-AllReduce collectives, so it is
      bit-for-bit :class:`CompressedAggregator` and serves as the
      innet arm's parity baseline; the tree is wire-model only (a tree
      of *float* adds would be order-sensitive and break that parity).

    The wire/occupancy story of the physical tree (bounded switch SRAM,
    streaming windows of ``cfg.switch_slots`` bucket chunks, per-port
    counters, straggler retransmit) is modeled by
    :class:`repro.net.switch.SwitchModel`, which the ``--compare-innet``
    benchmark drives over the same streams and pins against this
    strategy's output. The in-mesh collective streams the same windows
    (PR 5): the fxp32 tree reduces ``switch_slots`` buckets at a time
    (``tree_all_reduce(..., window_slots=...)``, matching the switch's
    slot pool window for window), and with ``cfg.overlap`` /
    ``cfg.stream_chunks`` the shared stream scheduler additionally
    pipelines window ``i``'s tree against window ``i+1``'s encode (the
    chunk grid spans whole switch windows; a forced ``stream_chunks``
    that cannot raises ``ValueError``).
    """

    wire = "compressed_innet"

    def _stream_plan(self, plan: BucketPlan) -> StreamPlan:
        """Chunks span whole ``switch_slots`` bucket windows, so the
        collective schedule and the SwitchModel slot pool agree."""
        return make_stream_plan(plan, self.cfg,
                                window_buckets=self.cfg.switch_slots,
                                base_block=self.base_block)

    def _encode(self, buckets: jnp.ndarray, plan: BucketPlan,
                comp: HomomorphicCompressor, dp_idx):
        cfg = self.cfg
        if cfg.wire_dtype == "f32":
            # Idealized float tier: same collectives (and bits) as
            # CompressedAggregator — including the streamed schedule,
            # whose chunks here span whole switch windows; see class
            # docstring. The tree is wire-model only on this dtype.
            make_topology(cfg.topology, self.mesh, self.dp_axes)  # validate
            return super()._encode(buckets, plan, comp, dp_idx)
        topo = make_topology(cfg.topology, self.mesh, self.dp_axes)
        wire = FixedPointWire(workers=self._dp_world())
        splan = self._stream_plan(plan)
        nbpb = splan.blocks_per_bucket

        @jax.named_scope("reduce")
        def tree_window(sk_buckets, maxabs_blocks, words_buckets):
            """One chunk (whole buckets) over the fxp32 tree, window by
            window: pmax-agree exponents from the producer's per-block
            maxabs byproduct (max-of-maxes == bucket max, exactly — no
            second pass over the sketch), quantize the Γ-compressed
            sketch, integer tree. The int32 sum and the agreed exponents
            ride the payload; dequantization happens inside the fused
            consumer pass (:meth:`_recover`)."""
            n_b = sk_buckets.shape[0]
            bucket_max = maxabs_blocks.reshape(n_b, nbpb).max(axis=1)
            exp = jax.lax.pmax(wire.exponents_from_maxabs(bucket_max),
                               tuple(self.dp_axes))
            q = tree_all_reduce(wire.encode(sk_buckets, exp), topo, "add",
                                axis_indices=dp_idx,
                                window_slots=cfg.switch_slots)
            w = tree_all_reduce(words_buckets, topo, "or",
                                axis_indices=dp_idx,
                                window_slots=cfg.switch_slots)
            return q, w, exp

        if not splan.streamed:
            with jax.named_scope("encode"):
                c, mx = comp.compress_wire(buckets.reshape(-1),
                                           block_offset=self.base_block)
            sk, words = c.sketch, c.index_words
            q_b, w_b, exp = tree_window(
                sk.reshape(plan.n_buckets, -1), mx,
                words.reshape(plan.n_buckets, splan.words_per_bucket))
            return q_b.reshape(sk.shape), w_b.reshape(-1), exp

        def red(payload):
            sk, words, mx = payload      # one chunk's local payload
            q_b, w_b, exp = tree_window(
                sk.reshape(splan.chunk_buckets, -1), mx,
                words.reshape(splan.chunk_buckets, splan.words_per_bucket))
            return q_b.reshape(sk.shape), w_b.reshape(words.shape), exp

        qs, ws, exps = self._encode_streamed(buckets, splan, comp, red,
                                             with_maxabs=True)
        q, w = self._trim_fused(qs, ws, plan, splan)
        return q, w, exps.reshape(-1)[:plan.n_buckets]

    def _recover(self, payload, plan: BucketPlan,
                 comp: HomomorphicCompressor, dp_idx, dp_rank,
                 spec_leaves=None):
        """fxp32 payloads carry ``(q int32, words, exponents)``: the
        exponent-bitcast dequantization runs *inside* the fused consumer
        pass (``recover(dequant=...)``) instead of as a separate
        sketch-sized decode before peeling. f32 payloads are the base
        class's ``(sketch, words)``."""
        if len(payload) == 2:
            return super()._recover(payload, plan, comp, dp_idx, dp_rank,
                                    spec_leaves=spec_leaves)
        q, words, exp = payload
        wire = FixedPointWire(workers=self._dp_world())
        nbpb = plan.blocks_per_bucket(self.cfg)
        with jax.named_scope("peel"):
            rec = comp.recover(
                CompressedLeaf(sketch=q, index_words=words), plan.padded,
                block_offset=self.base_block,
                dequant=(jnp.repeat(exp, nbpb), wire.mantissa_bits))
        return rec.reshape(plan.n_buckets, plan.bucket_elems)


# ----------------------------------------------------------------------
# The `auto` strategy (PR 6): execute controller-produced wire plans
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WirePlannedAggregator(CompressedAggregator):
    """The 5th registry strategy: per-bucket-group wire selection.

    Executes whatever :class:`~repro.core.wireplan.WirePlan` it is
    handed (``wire_plan=...``, produced by the
    :class:`~repro.core.costmodel.AutoWireController` host-side between
    steps); without one it falls back to the controller's *analytic*
    plan — ``strategy_wire_bytes`` plus the ``auto_*`` bandwidth priors,
    no telemetry — so the first compiled step is already a reasonable
    mixed plan. The compiled step is static per plan; the controller
    re-plans only every ``cfg.replan_every`` steps.

    Also the telemetry source: measures per-bucket occupancy of the
    aggregated stream into ``AggregationState.telemetry`` for the
    controller's feasibility test (occupancy near the peeling capacity
    rules the compressed wires out for that bucket).
    """

    wire = "auto"
    collect_telemetry = True

    def _wire_plan(self, plan: BucketPlan) -> WirePlan:
        if self.wire_plan is not None:
            return super()._wire_plan(plan)
        from .costmodel import analytic_plan  # late: costmodel imports us
        return analytic_plan(plan, self.cfg, workers=self._dp_world())


# ----------------------------------------------------------------------
# Expert-parallel all-to-all exchanges (the permute pattern, PR 8)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseAllToAllExchange:
    """Plain expert-parallel all-to-all over the shared bucket grid —
    the parity baseline for the compressed exchange.

    Unlike the aggregators (which build their own nested regions), an
    exchange is a plain callable used *inside* the model's manual
    region, where the EP axes are already bound: MoE dispatch/combine
    happens mid-forward, not at the gradient boundary.  Input: a pytree
    whose leaves carry a leading destination axis ``(W, ...)`` — lane
    ``d`` is this rank's payload for EP rank ``d`` (rank-major,
    :func:`~repro.core.collectives.linear_rank` order).  Output: the
    merged slice pytree ``sum_s payload_s[this_rank]`` (leaf shapes
    minus the lane axis) — the homomorphic combine lands at the
    receiving expert, never at a barrier.

    This baseline packs every lane into one per-destination
    :class:`~repro.core.bucketing.BucketPlan` grid (identical padding to
    the compressed wire, so the two are bit-comparable), ships the
    packed f32 stack over the permute lanes
    (:func:`~repro.core.collectives.alltoall_lane_sum`) and unpacks the
    merged slice.
    """

    wire = "dense"          # the pattern_wires("alltoall") entry executed
    pattern = "alltoall"

    cfg: CompressionConfig
    mesh: Any
    ep_axes: Tuple[str, ...]

    @property
    def workers(self) -> int:
        W = 1
        for ax in self.ep_axes:
            W *= self.mesh.shape[ax]
        return W

    def _ep_idx(self):
        return {ax: jax.lax.axis_index(ax) for ax in self.ep_axes}

    def _plan(self, payload) -> BucketPlan:
        return make_dest_bucket_plans(payload, self.cfg,
                                      n_dests=self.workers)[0]

    def _pack(self, payload, plan: BucketPlan) -> jnp.ndarray:
        """(W, ...) lane pytree -> (W, n_buckets, E) packed f32 stack."""
        return jnp.stack([
            plan.pack(jax.tree.map(lambda l: l[d], payload))
            for d in range(self.workers)])

    def __call__(self, payload):
        plan = self._plan(payload)
        stack = self._pack(payload, plan)
        merged = alltoall_lane_sum(
            stack, tuple(self.ep_axes), axis_indices=self._ep_idx(),
            combine="add")
        return plan.unpack(merged)


@dataclasses.dataclass(frozen=True)
class CompressedAllToAllExchange(DenseAllToAllExchange):
    """Compressed expert-parallel all-to-all: the first permute-pattern
    wire (PR 8).

    Each chunk of the per-destination bucket grid encodes in ONE
    producer pass (:meth:`HomomorphicCompressor.exchange_wire` — all
    ``W`` lanes in a single fused grid, chunk-major block ids), ships
    sketch + bitmap lanes over :func:`sketch_all_to_all` (W-1 ppermutes
    native, psum-emulated on a multi-axis EP), and the
    receiving rank recovers its merged lane in ONE consumer pass at the
    lane's global block offset — the PR 7 one-producer/one-consumer
    contract on the permute pattern.  The sketch add / bitmap OR on the
    wire IS the combine: what arrives is the compressed form of
    ``sum_s payload_s[this_rank]``, recovered without any rank ever
    holding another rank's raw payload.

    ``cfg.overlap`` / ``cfg.stream_chunks`` drive the lane chunks
    through the shared double-buffered
    :func:`~repro.core.streams.stream_schedule` (the chunk count must
    divide the per-destination bucket run; see
    :func:`~repro.core.streams.make_alltoall_stream_plan`), so chunk
    ``i``'s permutes hide chunk ``i+1``'s encode exactly like the
    all-reduce wires.  Bit-for-bit equal to
    :class:`DenseAllToAllExchange` on the same payloads in the
    exact-recovery regime (pinned by ``test_dispatch.py`` and the
    collectives driver).
    """

    wire = "compressed"

    def __call__(self, payload):
        cfg = self.cfg
        if cfg.index != "bitmap":
            raise ValueError(
                "the all-to-all exchange requires index='bitmap' (a "
                "Bloom filter hashes global coordinates and cannot be "
                "sliced per destination lane)")
        comp = HomomorphicCompressor(cfg)
        W = self.workers
        plan = self._plan(payload)
        stack = self._pack(payload, plan)          # (W, nb, E)
        splan = make_alltoall_stream_plan(plan, cfg, lanes=W)
        ep_idx = self._ep_idx()
        rank = linear_rank(self.ep_axes, ep_idx)

        def enc(i, chunk):                          # chunk: (W, cb, E)
            leaf, _ = comp.exchange_wire(
                chunk, block_offset=splan.chunk_start_block(i))
            return leaf.sketch, leaf.index_words

        def red(wire_payload):
            sk, words = wire_payload
            return sketch_all_to_all(sk, words, tuple(self.ep_axes),
                                     axis_indices=ep_idx)

        sks, ws = stream_schedule(splan.chunk_view(stack), enc, red)
        # sks (n_chunks, lane_blocks, rows, lanes) / ws (n_chunks, w):
        # this rank's merged lane per chunk. Peel each at the lane's
        # global block offset — same hash ids every source encoded it
        # under.

        def peel(args):
            j, sk_j, w_j = args
            return comp.recover(
                CompressedLeaf(sketch=sk_j, index_words=w_j),
                splan.chunk_elems,
                block_offset=splan.lane_start_block(j, rank))

        idx = jnp.arange(splan.n_chunks, dtype=jnp.int32)
        rec = jax.lax.map(peel, (idx, sks, ws))    # (n_chunks, chunk_elems)
        merged = rec.reshape(-1)[:plan.padded]
        return plan.unpack(merged.reshape(plan.n_buckets, plan.bucket_elems))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _exchange_vjp(exchange, payload):
    """Differentiable facade over an exchange executor.

    The exchange is *linear* — ``out_r = sum_s payload_s[r]`` — but the
    compressed path's peeling ``while_loop`` is not reverse-
    differentiable, so we install the exact linear transpose by hand:
    ``d payload_s[d] = d out_d`` (the cotangent each destination rank
    holds), i.e. an ``all_gather`` of the output cotangent over the EP
    axes back onto the lane axis.  Applied to both exchanges so the
    dense baseline and the compressed wire have identical gradient
    semantics.
    """
    return exchange(payload)


def _exchange_vjp_fwd(exchange, payload):
    return exchange(payload), None


def _exchange_vjp_bwd(exchange, _, g):
    axes = tuple(exchange.ep_axes)
    ct = jax.tree.map(
        lambda l: jax.lax.all_gather(l, axes, axis=0, tiled=False), g)
    return (ct,)


_exchange_vjp.defvjp(_exchange_vjp_fwd, _exchange_vjp_bwd)


@dataclasses.dataclass(frozen=True)
class _GradExchange:
    """What :func:`make_exchange` hands the model: the executor wrapped
    with its linear VJP, surface attributes passed through."""

    exchange: Any

    @property
    def workers(self) -> int:
        return self.exchange.workers

    @property
    def ep_axes(self) -> Tuple[str, ...]:
        return self.exchange.ep_axes

    @property
    def wire(self) -> str:
        return self.exchange.wire

    def __call__(self, payload):
        return _exchange_vjp(self.exchange, payload)


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------

AGGREGATORS = {
    "dense": DenseAggregator,
    "compressed": CompressedAggregator,
    "compressed_rs": CompressedReduceScatterAggregator,
    "compressed_innet": CompressedInNetworkAggregator,
    "auto": WirePlannedAggregator,
}

# The controller's search space (wireplan.WIRES) and the executable
# fixed strategies are the same set by construction — checked at import
# so they can never drift apart (satellite of PR 6).
assert set(WIRES) == set(AGGREGATORS) - {"auto"}, (
    f"wireplan.WIRES {WIRES} out of sync with AGGREGATORS "
    f"{sorted(AGGREGATORS)}")

# The permute-pattern executors (PR 8), keyed by the same wire names the
# plan layer validates for pattern='alltoall'. Deliberately a separate
# registry: exchanges are in-model callables (payload -> merged slice),
# not gradient aggregators, and `auto`/`fixed_wires` must not see them.
EXCHANGES = {
    "dense": DenseAllToAllExchange,
    "compressed": CompressedAllToAllExchange,
}

assert set(EXCHANGES) == set(pattern_wires("alltoall")), (
    f"wireplan alltoall wires {pattern_wires('alltoall')} out of sync "
    f"with EXCHANGES {sorted(EXCHANGES)}")


def make_aggregator(name: str, cfg: CompressionConfig, mesh,
                    dp_axes: Sequence[str],
                    tp_axes: Sequence[str] = ("model",),
                    mean: bool = True, outer_manual=None,
                    zero1_dims=None, wire_plan=None) -> Aggregator:
    """Build the named strategy (see :data:`AGGREGATORS`).

    ``outer_manual``: the axis set the calling shard_map takes manual
    (see :class:`CompressedAggregator.outer_manual`). ``zero1_dims``:
    per-leaf ZeRO-1 slice dims enabling the reduce-scatter gather-skip
    path (see :class:`CompressedAggregator.zero1_dims`). ``wire_plan``:
    an explicit per-bucket-group wire assignment (PR 6) — normally only
    set on the ``auto`` strategy by its controller.
    """
    if isinstance(dp_axes, str):
        dp_axes = (dp_axes,)
    if isinstance(tp_axes, str):
        tp_axes = (tp_axes,)
    try:
        cls = AGGREGATORS[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {name!r}; have {sorted(AGGREGATORS)}")
    return cls(cfg=cfg, mesh=mesh, dp_axes=tuple(dp_axes),
               tp_axes=tuple(tp_axes), mean=mean,
               outer_manual=None if outer_manual is None
               else tuple(outer_manual),
               zero1_dims=None if zero1_dims is None else tuple(zero1_dims),
               wire_plan=wire_plan)


def make_exchange(name: str, cfg: CompressionConfig, mesh,
                  ep_axes: Sequence[str]):
    """Build the named all-to-all exchange (see :data:`EXCHANGES`).

    Returns a differentiable callable for use *inside* a manual region
    where ``ep_axes`` are bound: ``(W, ...)`` lane pytree -> merged
    slice pytree (``sum_s payload_s[this_rank]``), with ``.workers`` /
    ``.ep_axes`` / ``.wire`` exposed for the caller's geometry checks.
    """
    if isinstance(ep_axes, str):
        ep_axes = (ep_axes,)
    try:
        cls = EXCHANGES[name]
    except KeyError:
        raise ValueError(
            f"unknown exchange {name!r}; have {sorted(EXCHANGES)}")
    return _GradExchange(exchange=cls(
        cfg=cfg, mesh=mesh, ep_axes=tuple(ep_axes)))
