"""Configuration for the lossless homomorphic compressor.

The knobs mirror the paper's design space:

- ``ratio``      — compressed sketch cells / original elements (the paper
                   sweeps 2%..200%; its end-to-end runs fix 10%).
- ``lanes``      — the locality batch width ``c`` of §3.4. On GPU the paper
                   uses 1024 (threads per block); on TPU we default to 512
                   = 4 x 128 so a batch row is lane-aligned in VMEM.
- ``rows``       — sketch rows per block, split into 3 hash partitions
                   (3-partite hypergraph, peeling threshold gamma = 1.23).
- ``rounds``     — peeling iterations; the paper proves log log n + O(1)
                   and reaches O(1) by splitting the sketch into fixed-size
                   blocks, which is structural here.
- ``index``      — "bitmap" (exact, 1 bit/coordinate, §3.2) or "bloom"
                   (probabilistic, §3.3, for extreme sparsity).
- ``bucket_bytes`` / ``overlap`` — the aggregation substrate (PR 2): the
                   whole gradient pytree is packed into fixed-byte flat
                   buckets before encoding (see
                   :mod:`repro.core.bucketing`), so the codec and the
                   collectives launch O(n_buckets) times instead of
                   O(n_leaves); ``overlap`` stages bucket *i*'s
                   collectives against bucket *i+1*'s encode.
- ``wire_dtype`` / ``switch_slots`` / ``topology`` — the in-network
                   aggregation tier (PR 4): the ``compressed_innet``
                   strategy ships the sketch over an emulated
                   programmable-switch tree (:mod:`repro.net`),
                   optionally quantized to overflow-free fixed point.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

GAMMA = 1.23  # 3-ary peeling threshold from the paper (§3.2)


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Static plan for the homomorphic compressor (hashable, jit-friendly)."""

    ratio: float = 0.10          # sketch elements / original elements
    lanes: int = 512             # batch width c (multiple of 128 on TPU)
    rows: int = 6                # sketch rows per block; divisible by 3
    rounds: int = 10             # peeling iteration cap (while_loop exits
                                 # at fixpoint; log log n + O(1) expected)
    index: str = "bitmap"        # "bitmap" | "bloom"
    bloom_hashes: int = 3        # k for the Bloom filter variant
    bloom_bits_ratio: float = 0.125  # bloom bits per original element
    topk_ratio: Optional[float] = None   # optional sparsity budget
    topk_exact: bool = False     # exact lax.top_k (O(n log n) sort buffers)
                                 # vs sampled-quantile threshold (O(n))
    error_feedback: bool = True  # accumulate unsent residual (DGC-style)
    seed: int = 0x5EED
    chunk_blocks: int = 512      # blocks per lax.map chunk (memory bound)
    use_pallas: str = "auto"     # "never" | "always" | "auto"
    encode_block_tile: int = 8   # sketch blocks per encode-kernel grid
                                 # cell (VMEM-bounded; see sketch_encode)
    peel_block_tile: int = 8     # sketch blocks per peel-kernel grid cell
                                 # (the peel loop runs block by block in
                                 # VMEM scratch, so its state does not
                                 # grow with the tile)
    bucket_bytes: int = 4 << 20  # target f32 bytes per aggregation bucket
                                 # (rounded to block/word alignment; see
                                 # bucketing.BucketPlan)
    overlap: bool = False        # pipeline chunk i's collectives against
                                 # chunk i+1's encode through the shared
                                 # stream scheduler (core/streams.py);
                                 # the default grid is the finest aligned
                                 # one (per bucket on the AllReduce wire,
                                 # per rank-chunk on the native RS wire,
                                 # per switch window on the innet tree)
    stream_chunks: Optional[int] = None
                                 # explicit wire-chunk count for the
                                 # stream scheduler (implies overlap).
                                 # Must respect the strategy's alignment
                                 # constraints: divide ceil(n_buckets/W)
                                 # on the native RS wire, span whole
                                 # switch_slots windows on the innet
                                 # tree (ValueError otherwise); any
                                 # count is valid on the AllReduce wire
                                 # (non-divisible grids zero-pad).
    rs_wire: str = "auto"        # reduce-scatter strategy wire path:
                                 # "auto" / "native" — psum_scatter +
                                 #             OR-RS (every region);
                                 # "emulate" — the psum+slice emulation
                                 #             (parity tests / benchmarks)
    wire_dtype: str = "f32"      # compressed_innet sketch wire (PR 4):
                                 # "f32"   — idealized float-capable
                                 #           aggregation tier (bit-parity
                                 #           with 'compressed');
                                 # "fxp32" — per-bucket shared-exponent
                                 #           int32, overflow-free for the
                                 #           DP world size — what a real
                                 #           switch can sum (see
                                 #           repro.net.fixedpoint)
    switch_slots: int = 8        # emulated switch SRAM aggregation slots
                                 # (bucket-chunks resident per streaming
                                 # window; see repro.net.switch)
    topology: str = "flat"       # in-network reduction tree: "flat" (one
                                 # switch) | "tor_spine" (one tier per DP
                                 # axis; see repro.net.topology)
    sketch_dtype: str = "float32"
    # ---- `auto` strategy cost-model knobs (PR 6) ---------------------
    replan_every: int = 16       # steps between wire-plan refreshes for
                                 # the `auto` strategy; the compiled step
                                 # is static per plan, so this bounds
                                 # recompilation frequency
    auto_link_gbps: float = 400.0  # analytic prior: link bandwidth used
                                 # to turn strategy_wire_bytes into
                                 # seconds before any telemetry exists.
                                 # Default = the per-link ICI roofline
                                 # (costmodel.ICI_BW, 50 GB/s); override
                                 # from benchmarks/roofline.py --codec
                                 # via costmodel.priors_from_codec_report
    auto_codec_gbps: float = 6552.0  # analytic prior: codec streaming
                                 # throughput (bytes of bucket stream
                                 # per second PER PASS) for the
                                 # codec-compute term. Default = the
                                 # HBM roofline (costmodel.HBM_BW,
                                 # 819 GB/s); the per-wire pass counts
                                 # (kernels.ops.wire_codec_passes) turn
                                 # this into seconds
    auto_occupancy_margin: float = 0.9
                                 # compressed wires are infeasible for a
                                 # bucket whose measured nonzero count
                                 # exceeds this fraction of the peeling
                                 # capacity (recovery would go lossy);
                                 # such buckets are planned dense

    def __post_init__(self):
        if self.rows % 3 != 0 or self.rows < 3:
            raise ValueError(f"rows must be a positive multiple of 3, got {self.rows}")
        if not 0.0 < self.ratio:
            raise ValueError(f"ratio must be positive, got {self.ratio}")
        if self.lanes < 8:
            raise ValueError(f"lanes must be >= 8, got {self.lanes}")
        if self.index not in ("bitmap", "bloom"):
            raise ValueError(f"index must be 'bitmap' or 'bloom', got {self.index}")
        if self.encode_block_tile < 1:
            raise ValueError(
                f"encode_block_tile must be >= 1, got {self.encode_block_tile}")
        if self.peel_block_tile < 1:
            raise ValueError(
                f"peel_block_tile must be >= 1, got {self.peel_block_tile}")
        if self.bucket_bytes < 4:
            raise ValueError(
                f"bucket_bytes must be >= 4, got {self.bucket_bytes}")
        if (self.overlap or self.stream_chunks is not None) \
                and self.index != "bitmap":
            # Per-chunk OR collectives slice the packed bitmap by bucket;
            # a Bloom filter is one global structure and cannot be sliced.
            raise ValueError(
                "overlap/stream_chunks require index='bitmap'")
        if self.stream_chunks is not None and self.stream_chunks < 1:
            raise ValueError(
                f"stream_chunks must be >= 1, got {self.stream_chunks}")
        if self.rs_wire not in ("auto", "native", "emulate"):
            raise ValueError(
                f"rs_wire must be 'auto', 'native' or 'emulate', "
                f"got {self.rs_wire!r}")
        if self.wire_dtype not in ("f32", "fxp32"):
            raise ValueError(
                f"wire_dtype must be 'f32' or 'fxp32', got "
                f"{self.wire_dtype!r}")
        if self.switch_slots < 1:
            raise ValueError(
                f"switch_slots must be >= 1, got {self.switch_slots}")
        if self.topology not in ("flat", "tor_spine"):
            raise ValueError(
                f"topology must be 'flat' or 'tor_spine', got "
                f"{self.topology!r}")
        if self.replan_every < 1:
            raise ValueError(
                f"replan_every must be >= 1, got {self.replan_every}")
        if self.auto_link_gbps <= 0 or self.auto_codec_gbps <= 0:
            raise ValueError(
                f"auto_link_gbps/auto_codec_gbps must be positive, got "
                f"{self.auto_link_gbps}/{self.auto_codec_gbps}")
        if not 0.0 < self.auto_occupancy_margin <= 1.0:
            raise ValueError(
                f"auto_occupancy_margin must be in (0, 1], got "
                f"{self.auto_occupancy_margin}")

    # ---- derived static geometry -------------------------------------

    @property
    def group(self) -> int:
        """G — gradient batches per sketch block (rows / ratio)."""
        return max(1, round(self.rows / self.ratio))

    @property
    def block_elems(self) -> int:
        """Original elements covered by one block."""
        return self.group * self.lanes

    @property
    def sketch_elems(self) -> int:
        """Sketch cells per block."""
        return self.rows * self.lanes

    @property
    def peel_capacity(self) -> int:
        """Max non-zeros per block recoverable w.h.p. (|Y| / gamma)."""
        return int(self.sketch_elems / GAMMA)

    def num_blocks(self, n: int) -> int:
        """Blocks needed to cover ``n`` elements."""
        return -(-n // self.block_elems)

    def padded_size(self, n: int) -> int:
        return self.num_blocks(n) * self.block_elems

    # ---- bucket geometry (PR 2 aggregation substrate) ----------------

    @property
    def bucket_quantum(self) -> int:
        """Alignment unit for bucket sizes: whole sketch blocks *and*
        whole packed-bitmap uint32 words, so per-bucket sketch / index
        slices of the fused stream are exact views."""
        return math.lcm(self.block_elems, 32)

    def bucket_elems_for(self, total_elems: int) -> int:
        """f32 elements per bucket for a stream of ``total_elems``.

        ``bucket_bytes`` rounded up to the alignment quantum, but never
        larger than the (quantum-rounded) stream itself — a pytree
        smaller than one configured bucket gets a single right-sized
        bucket instead of megabytes of zero padding.
        """
        if total_elems < 1:
            raise ValueError(f"total_elems must be >= 1, got {total_elems}")
        q = self.bucket_quantum
        want = max(1, self.bucket_bytes // 4)
        elems = -(-want // q) * q
        cap = -(-total_elems // q) * q
        return min(elems, cap)

    def num_buckets(self, total_elems: int) -> int:
        return -(-total_elems // self.bucket_elems_for(total_elems))

    def wire_bytes(self, n: int, grad_bytes_per_elem: int = 2) -> dict:
        """Strategy-agnostic payload sizes for ``n`` elements.

        These are the sizes of the *objects* that cross the wire — the
        fp32 sketch (``sketch_bytes``), the packed index
        (``index_bytes``, 1 bit/element bitmap or the Bloom filter), and
        the dense baseline gradient (``dense_bytes``) — NOT what any
        particular collective ships per rank: an AllReduce materializes
        the whole reduced payload on every rank while a reduce-scatter
        lands only ``1/W`` of it, and link traffic further depends on
        the algorithm (ring AllReduce moves ``2(W-1)/W x`` payload per
        rank, a reduce-scatter ``(W-1)/W x``). For per-rank,
        per-strategy accounting use :meth:`strategy_wire_bytes`.

        Includes the per-bucket totals of the bucketed aggregation path:
        ``n`` is taken as the whole packed stream, split into
        ``n_buckets`` buckets of ``bucket_elems`` each (last one padded),
        and each bucket ships ``bucket_sketch_bytes + bucket_index_bytes``.
        """
        nb = self.num_blocks(n)
        sketch = nb * self.sketch_elems * 4  # fp32 sketch
        if self.index == "bitmap":
            idx = -(-self.padded_size(n) // 32) * 4  # 1 bit / elem, packed u32
        else:
            idx = int(n * self.bloom_bits_ratio / 32 + 1) * 4
        dense = n * grad_bytes_per_elem
        be = self.bucket_elems_for(n)
        n_buckets = self.num_buckets(n)
        b_sketch = (be // self.block_elems) * self.sketch_elems * 4
        if self.index == "bitmap":
            b_idx = (be // 32) * 4
        else:
            b_idx = int(be * self.bloom_bits_ratio / 32 + 1) * 4
        return {
            "sketch_bytes": sketch,
            "index_bytes": idx,
            "total_bytes": sketch + idx,
            "dense_bytes": dense,
            "wire_fraction": (sketch + idx) / max(dense, 1),
            "n_buckets": n_buckets,
            "bucket_elems": be,
            "bucket_sketch_bytes": b_sketch,
            "bucket_index_bytes": b_idx,
            "bucket_total_bytes": b_sketch + b_idx,
            "bucketed_total_bytes": n_buckets * (b_sketch + b_idx),
        }

    def strategy_wire_bytes(self, n: int, workers: int,
                            grad_bytes_per_elem: int = 2,
                            zero1_aligned: bool = False) -> dict:
        """Per-rank wire accounting for each aggregation strategy.

        For a stream of ``n`` elements reduced across ``workers`` (W)
        ranks, reports for every strategy in
        :data:`repro.core.aggregators.AGGREGATORS` (the reduce-scatter
        one split into its native and emulated wire paths):

        - ``rank_payload_bytes`` — the reduced payload that *lands on*
          each rank after its collectives: the full dense gradient /
          full sketch+index for the AllReduce strategies, but only the
          ``1/W`` sketch+bitmap slice for the native reduce-scatter
          path (padded to whole per-rank bucket chunks). This is the
          number the paper's "aggregatable at full collective
          bandwidth" claim is about.
        - ``link_bytes`` — bytes each rank *sends* under the standard
          bandwidth-optimal algorithms: ring AllReduce at
          ``2(W-1)/W x`` payload, reduce-scatter at ``(W-1)/W x``. The
          in-network tree sends the payload exactly **once** up the
          worker's access link (switches combine in flight), so its
          ``link_bytes`` is ``1 x`` payload.
        - ``root_link_bytes`` (``compressed_innet`` only) — what the
          tree's root link carries per direction: the aggregated stream
          crosses it once no matter how many workers hang below
          (``payload/fanout`` per child, amortized), vs every ring
          link carrying ``2(W-1)/W x`` payload. With
          ``wire_dtype='fxp32'`` the payload additionally ships one
          int32 shared exponent per bucket (``exponent_bytes``); the
          per-tier switch ingress/occupancy numbers live in
          :meth:`repro.net.topology.Topology.link_profile` and the
          ``SwitchModel`` report, which need the concrete topology.

        The compressed payloads are those of the *bucket-padded* packed
        stream (``n_buckets x bucket_elems`` elements) — what the
        bucketed aggregators actually encode and ship — further padded
        to whole per-rank chunks of ``ceil(n_buckets/W)`` buckets for
        the native RS arm. (With fewer buckets than ranks that chunk
        padding can erase the native win entirely: one bucket over two
        ranks scatters nothing.) Other caveats: the numbers model the
        *native* collectives; where the OR is psum-emulated (the
        multi-axis all-to-all) it ships 32x the bitmap's wire volume
        (``or_emulated_factor`` scales index traffic for that path).

        ``compressed_rs``'s native path reports the recovered-chunk
        all_gather separately: ``link_bytes_with_gather`` counts it,
        ``link_bytes_no_gather`` does not (the psum-trick fallback ships
        2x ``rs_gather_link_bytes``), and ``link_bytes`` — the number
        the ``--compare-rs`` CI gate measures — picks between them by
        ``zero1_aligned``: pass True when the stream chunk grid aligns
        with the ZeRO-1 optimizer slices
        (:func:`repro.core.streams.zero1_gather_skip`), where the
        aggregator feeds the per-rank recovered chunks straight into the
        optimizer shards and the gather is skipped entirely.

        Every entry names its collective ``pattern`` (PR 8): the
        aggregation strategies above are ``allreduce``; the
        ``dense_alltoall`` / ``compressed_alltoall`` entries model the
        expert-parallel permute wire, where ``n`` is this rank's
        *stacked* W-lane dispatch/combine payload and each rank
        sends/receives ``(W-1)/W x`` of it (its own lane stays local).
        """
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        W = workers
        base = self.wire_bytes(n, grad_bytes_per_elem)
        dense = base["dense_bytes"]
        nb = base["n_buckets"]
        be = base["bucket_elems"]

        def payload(n_buckets: int):
            """sketch+index bytes of ``n_buckets`` whole buckets."""
            elems = n_buckets * be
            sketch = (elems // self.block_elems) * self.sketch_elems * 4
            if self.index == "bitmap":
                return sketch, (elems // 32) * 4
            return sketch, int(elems * self.bloom_bits_ratio / 32 + 1) * 4

        full = sum(payload(nb))
        # Native RS pads the stream to whole per-rank chunks of buckets.
        nb_p = -(-nb // W) * W
        if self.index == "bitmap":
            sketch_p, idx_p = payload(nb_p)
        else:
            idx_p = None  # Bloom cannot be sliced: no native RS wire
        ring = 2 * (W - 1) / W
        rs = (W - 1) / W
        out = {
            "workers": W,
            "elems": n,
            "or_emulated_factor": 32,
            "dense": {
                "rank_payload_bytes": dense,
                "link_bytes": int(dense * ring),
            },
            "compressed": {
                "rank_payload_bytes": full,
                "link_bytes": int(full * ring),
            },
            # Emulated RS reduces the full sketch+index on every rank
            # (psum + local slice): AllReduce wire, RS compute only.
            "compressed_rs_emulated": {
                "rank_payload_bytes": full,
                "link_bytes": int(full * ring),
            },
        }
        if idx_p is not None:
            rs_link = int((sketch_p + idx_p) * rs)
            gather = int(nb_p * be * 4 * rs)
            out["compressed_rs_native"] = {
                "rank_payload_bytes": (sketch_p + idx_p) // W,
                "rs_gather_link_bytes": gather,
                "link_bytes_with_gather": rs_link + gather,
                "link_bytes_no_gather": rs_link,
                "zero1_aligned": zero1_aligned,
                "link_bytes": rs_link + (0 if zero1_aligned else gather),
            }
        else:
            out["compressed_rs_native"] = None
        # In-network tree (PR 4): the bucket-padded stream goes up the
        # tree once per worker and comes back once; no per-rank chunk
        # padding (every rank receives the whole aggregate).
        exp_bytes = nb * 4 if self.wire_dtype == "fxp32" else 0
        innet = full + exp_bytes
        out["compressed_innet"] = {
            "rank_payload_bytes": innet,
            "link_bytes": innet if W > 1 else 0,
            "root_link_bytes": innet if W > 1 else 0,
            "exponent_bytes": exp_bytes,
        }
        for entry in out.values():
            if isinstance(entry, dict):
                entry["pattern"] = "allreduce"
        # ---- the permute pattern (PR 8) ------------------------------
        # ``n`` is reinterpreted as this rank's *stacked* all-to-all
        # payload (all W destination lanes); each destination's slice of
        # ceil(n/W) elements gets its own bucket run. Every rank keeps
        # its own lane local and sends/receives the other W-1 —
        # (W-1)/W x the stacked payload each way, the all-to-all analogue
        # of the reduce-scatter factor. The compressed wire ships the
        # sketch+bitmap of each lane instead of the raw slice; the
        # psum-emulation fallback (multi-axis EP)
        # reduces the whole stack at ring AllReduce volume
        # (``link_bytes_emulated``; bitmap additionally at
        # ``or_emulated_factor``).
        n_d = -(-n // W)                  # per-destination slice elems
        be_d = self.bucket_elems_for(n_d)
        nb_d = self.num_buckets(n_d)
        lane_elems = nb_d * be_d
        lane_sketch = (lane_elems // self.block_elems) * self.sketch_elems * 4
        if self.index == "bitmap":
            lane_idx = (lane_elems // 32) * 4
        else:
            lane_idx = int(lane_elems * self.bloom_bits_ratio / 32 + 1) * 4
        lane_bytes = lane_sketch + lane_idx
        comp_stack = W * lane_bytes
        out["dense_alltoall"] = {
            "pattern": "alltoall",
            "payload_bytes": dense,
            "rank_payload_bytes": int(dense * rs),
            "link_bytes": int(dense * rs),
        }
        out["compressed_alltoall"] = {
            "pattern": "alltoall",
            "n_lane_buckets": nb_d,
            "lane_payload_bytes": lane_bytes,
            "payload_bytes": comp_stack,
            "rank_payload_bytes": int(comp_stack * rs),
            "link_bytes": int(comp_stack * rs),
            "link_bytes_emulated": int(comp_stack * ring),
        }
        return out
