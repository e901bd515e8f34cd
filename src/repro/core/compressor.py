"""Top-level homomorphic compressor (paper Algorithm 1).

``HomomorphicCompressor`` turns a gradient leaf (any shape) into the wire
format ``CompressedLeaf(sketch, index_words)`` and back:

    compress:  X -> S(X) = [Y, B]          (phase I)
    recover :  S(sum X) -> sum X           (phase II, peeling + estimate)

Both directions are pure jittable functions of statically-planned shape.
Aggregation happens *between* the two calls and is someone else's job —
``psum`` for the sketch, OR-AllReduce for the index words (see
:mod:`repro.core.aggregators`, which feeds the compressor whole bucketed
gradient streams, and :mod:`repro.core.collectives` for the primitives) —
which is exactly the homomorphic contract of the paper: the aggregation
API never decompresses. ``block_offset`` lets a caller encode/recover a
sub-range of a larger bucket stream under the stream's global hash plan.

All sketch compute (encode, peel, estimate) goes through the backend
dispatch in :mod:`repro.kernels.ops`, so ``cfg.use_pallas`` selects the
Pallas TPU kernels or the jnp reference for every consumer of this class.

Large leaves are processed in chunks of ``cfg.chunk_blocks`` blocks via
``lax.map`` to bound peak memory (the (nb, G, 3, c) rotation intermediates
would otherwise dwarf the gradient itself).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .config import CompressionConfig
from .blocks import LeafPlan, make_plan, to_blocks, from_blocks
from . import index as index_lib


class CompressedLeaf(NamedTuple):
    """Wire format for one leaf. Sketch aggregates by +, words by |."""
    sketch: jnp.ndarray       # (nb, rows, lanes) f32
    index_words: jnp.ndarray  # (w,) uint32 — packed bitmap or Bloom filter


class RecoveryStats(NamedTuple):
    nnz: jnp.ndarray          # indexed coordinates (candidates)
    peeled: jnp.ndarray       # exactly recovered
    residual: jnp.ndarray     # fell back to median estimate
    rounds: jnp.ndarray       # peeling rounds used


def chunked_map(fn, nb: int, chunk: int, *arrays):
    """lax.map ``fn`` over blocks in chunks; pads nb to a chunk multiple.

    ``arrays`` all have leading dim nb. Padding blocks are all-zero, which
    is harmless for both encode (zero sketch) and peel (empty index).
    """
    if nb <= chunk:
        return fn(*arrays)
    nchunks = -(-nb // chunk)
    padded = nchunks * chunk

    def pad(a):
        return jnp.pad(a, [(0, padded - nb)] + [(0, 0)] * (a.ndim - 1))

    stacked = [pad(a).reshape((nchunks, chunk) + a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda args: fn(*args), tuple(stacked))
    return jax.tree.map(
        lambda o: o.reshape((padded,) + o.shape[2:])[:nb], out)


@dataclasses.dataclass(frozen=True)
class HomomorphicCompressor:
    cfg: CompressionConfig

    # ------------------------------------------------------------------
    # Phase I — compression
    # ------------------------------------------------------------------

    def compress_wire(self, x: jnp.ndarray, block_offset=0
                      ) -> Tuple[CompressedLeaf, jnp.ndarray]:
        """One wire-producer pass: ``(CompressedLeaf, per-block maxabs)``.

        On fused-capable geometries (`ops.fused_wire_supported`) this is
        ONE pass over the gradient stream — sketch, packed bitmap and the
        per-block max magnitude come out of a single
        `ops.encode_pack_quantize` grid pass (the maxabs feeds the fxp32
        shared-exponent `pmax`; max is exact, so max-of-block-maxes ==
        bucket max, bit for bit). Bloom / unaligned geometries fall back
        to the composed encode-then-pack passes.

        ``block_offset`` (static or traced int32) shifts the hash/
        rotation block ids — used by the bucketed aggregators so a bucket
        encoded on its own is bit-identical to its slice of the fused
        whole-stream encode (the block at stream position ``b`` always
        hashes as block ``b``).
        """
        plan = make_plan(x.size, self.cfg)
        xb = to_blocks(x.astype(jnp.float32), plan)
        ids = jnp.arange(plan.nb, dtype=jnp.int32) + jnp.int32(block_offset)

        if ops.fused_wire_supported(self.cfg):
            def enc(ids_c, xb_c):
                return ops.encode_pack_quantize(xb_c, ids_c, self.cfg)

            sketch, words2d, maxabs = chunked_map(
                enc, plan.nb, self.cfg.chunk_blocks, ids, xb)
            return (CompressedLeaf(sketch=sketch,
                                   index_words=words2d.reshape(-1)),
                    maxabs)

        def enc(ids_c, xb_c):
            return ops.sketch_encode(xb_c, ids_c, self.cfg)

        sketch = chunked_map(enc, plan.nb, self.cfg.chunk_blocks, ids, xb)
        if self.cfg.index == "bitmap":
            words = index_lib.pack_bits(index_lib.bitmap_build(xb))
        else:
            words = index_lib.bloom_build(xb, self.cfg)
        maxabs = jnp.max(jnp.abs(sketch), axis=(1, 2))
        return CompressedLeaf(sketch=sketch, index_words=words), maxabs

    def compress(self, x: jnp.ndarray, block_offset=0) -> CompressedLeaf:
        """Wire payload only — see :meth:`compress_wire`."""
        return self.compress_wire(x, block_offset=block_offset)[0]

    def exchange_wire(self, lane_buckets: jnp.ndarray, block_offset=0
                      ) -> Tuple[CompressedLeaf, jnp.ndarray]:
        """One producer pass for the permute-pattern wire (PR 8).

        ``lane_buckets`` is one chunk of the all-to-all payload:
        ``(lanes, chunk_buckets, bucket_elems)`` — one bucket slab per
        destination lane, laid out chunk-major so the whole stack is a
        single *contiguous* block range starting at ``block_offset``.
        That keeps the PR 7 one-producer contract: the entire chunk —
        every lane — encodes in ONE :meth:`compress_wire` pass (one
        fused `encode_pack_quantize` grid on capable geometries), and
        the per-lane payloads are pure reshaped views of that pass:

            sketch      (lanes, lane_blocks, rows, cfg.lanes)
            index_words (lanes, lane_words)

        Lane ``d`` of the result is bit-identical to compressing lane
        ``d``'s slab alone at offset ``block_offset + d * lane_blocks``
        — the property the all-to-all merge relies on (every source
        rank encodes destination ``d``'s slab under the same hash ids,
        so the ppermuted sketches add homomorphically).  Also returns
        the per-block maxabs reshaped per lane, ``(lanes,
        lane_blocks)``.
        """
        lanes, nb_c, elems = lane_buckets.shape
        if elems % self.cfg.block_elems:
            raise ValueError(
                f"bucket_elems {elems} is not a whole number of sketch "
                f"blocks ({self.cfg.block_elems})")
        comp, maxabs = self.compress_wire(
            lane_buckets.reshape(-1), block_offset=block_offset)
        lane_blocks = (nb_c * elems) // self.cfg.block_elems
        sk = comp.sketch.reshape((lanes, lane_blocks) + comp.sketch.shape[1:])
        wd = comp.index_words.reshape(lanes, -1)
        return (CompressedLeaf(sketch=sk, index_words=wd),
                maxabs.reshape(lanes, lane_blocks))

    # ------------------------------------------------------------------
    # Phase II — recovery
    # ------------------------------------------------------------------

    def recover(self, comp: CompressedLeaf, n: int, shape=None,
                with_stats: bool = False, block_offset=0, dequant=None
                ) -> jnp.ndarray | Tuple[jnp.ndarray, RecoveryStats]:
        """``block_offset``: hash-plan id of the first block in
        ``comp`` — pass the same offset the sketch was encoded with when
        recovering a sub-range of a fused bucket stream (bitmap index
        only: a Bloom filter hashes global coordinates and cannot be
        sliced per-range).

        ``dequant``: optional ``(per_block_exponents (nb,) int32,
        mantissa_bits int)`` — the aggregated int32 fxp32 sketch is then
        dequantized *inside* the fused consumer pass (exponent-bitcast
        scale, see `net/fixedpoint.py`) instead of in a separate
        stream-sized op before peeling.

        On fused-capable geometries the whole receive side — bitmap
        unpack, optional dequant, peel — is ONE pass over the wire
        payload (`ops.dequant_peel_unpack`); recovery stats come from a
        `population_count` over the packed words, never materializing
        the unpacked bitmap outside the kernel.
        """
        plan = make_plan(n, self.cfg)
        bshape = (plan.nb, plan.group, plan.lanes)
        ids = jnp.arange(plan.nb, dtype=jnp.int32) + jnp.int32(block_offset)

        if ops.fused_wire_supported(self.cfg):
            wpb = self.cfg.block_elems // 32
            words2d = comp.index_words.reshape(plan.nb, wpb)
            if dequant is not None:
                exps, mbits = dequant

                def rec(ids_c, sk_c, w_c, e_c):
                    return ops.dequant_peel_unpack(
                        sk_c, w_c, ids_c, self.cfg,
                        exponents=e_c, mantissa_bits=mbits)

                values, residual = chunked_map(
                    rec, plan.nb, self.cfg.chunk_blocks,
                    ids, comp.sketch, words2d,
                    jnp.asarray(exps, jnp.int32))
            else:
                def rec(ids_c, sk_c, w_c):
                    return ops.dequant_peel_unpack(sk_c, w_c, ids_c, self.cfg)

                values, residual = chunked_map(
                    rec, plan.nb, self.cfg.chunk_blocks,
                    ids, comp.sketch, words2d)
            nnz = jnp.sum(jax.lax.population_count(comp.index_words)
                          ).astype(jnp.int32)
        else:
            if self.cfg.index == "bitmap":
                bits = index_lib.unpack_bits(comp.index_words, bshape)
            else:
                bits = index_lib.bloom_query(bshape, self.cfg,
                                             comp.index_words)
            sketch = comp.sketch
            if dequant is not None:
                exps, mbits = dequant
                from repro.net.fixedpoint import pow2
                scale = pow2(jnp.asarray(exps, jnp.int32) - int(mbits))
                sketch = sketch.astype(jnp.float32) * scale[:, None, None]

            def rec(ids_c, sk_c, bits_c):
                return ops.sketch_peel(sk_c, bits_c, ids_c, self.cfg)

            values, residual = chunked_map(
                rec, plan.nb, self.cfg.chunk_blocks, ids, sketch, bits)
            nnz = jnp.sum(bits)
        x = from_blocks(values, plan, shape)
        if not with_stats:
            return x
        n_residual = jnp.sum(residual.astype(jnp.int32))
        stats = RecoveryStats(
            nnz=nnz, peeled=nnz - n_residual,   # peeled == indexed & exact
            residual=n_residual, rounds=jnp.int32(self.cfg.rounds))
        return x, stats

    # ------------------------------------------------------------------
    # Lossy sketch-only decode (Sketched-SGD style) for ablations
    # ------------------------------------------------------------------

    def estimate(self, comp: CompressedLeaf, n: int, shape=None,
                 block_offset=0) -> jnp.ndarray:
        plan = make_plan(n, self.cfg)
        ids = jnp.arange(plan.nb, dtype=jnp.int32) + jnp.int32(block_offset)

        def est(ids_c, sk_c):
            return ops.sketch_estimate(sk_c, ids_c, self.cfg)

        values = chunked_map(est, plan.nb, self.cfg.chunk_blocks, ids, comp.sketch)
        if self.cfg.index == "bitmap":
            bits = index_lib.unpack_bits(
                comp.index_words, (plan.nb, plan.group, plan.lanes))
            values = jnp.where(bits, values, 0.0)
        return from_blocks(values, plan, shape)

    # ------------------------------------------------------------------
    # Wire accounting
    # ------------------------------------------------------------------

    def wire_bytes(self, n: int, grad_bytes_per_elem: int = 2) -> dict:
        return self.cfg.wire_bytes(n, grad_bytes_per_elem)
