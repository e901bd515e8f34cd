"""Jitted dispatch between the Pallas kernels and the jnp reference.

This module is the **single compute backend** for the compression
pipeline: ``HomomorphicCompressor`` (and through it training, serving,
collectives and the benchmarks) calls ``sketch_encode`` / ``sketch_peel``
/ ``sketch_estimate`` here and never reaches into ``repro.core.sketch``
or ``repro.core.peeling`` directly, so the ``use_pallas`` policy governs
every consumer.

``use_pallas`` policy:
  "never"  — always the jnp reference (the default on CPU: interpret-mode
             Pallas is a Python-loop emulator, far slower than XLA:CPU).
  "always" — Pallas, interpret=True off-TPU so the kernel body still
             executes (correctness path used by the test suite).
  "auto"   — Pallas on TPU backends, reference elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.config import CompressionConfig
from . import ref as ref_ops
from .sketch_encode import sketch_encode_pallas
from .sketch_peel import sketch_peel_pallas
from .sketch_wire import (encode_pack_quantize_pallas,
                          dequant_peel_unpack_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _want_pallas(cfg: CompressionConfig) -> bool:
    if cfg.use_pallas == "never":
        return False
    if cfg.use_pallas == "always":
        return True
    return _on_tpu()


def sketch_encode(xb: jnp.ndarray, block_ids: jnp.ndarray,
                  cfg: CompressionConfig) -> jnp.ndarray:
    """(nb, G, c) values + (nb,) ids -> (nb, rows, c) sketch (f32)."""
    if _want_pallas(cfg):
        return sketch_encode_pallas(xb, block_ids, cfg,
                                    interpret=not _on_tpu())
    return ref_ops.sketch_encode_ref(xb, block_ids, cfg)


def sketch_peel(sketch: jnp.ndarray, bits: jnp.ndarray,
                block_ids: jnp.ndarray, cfg: CompressionConfig):
    """(nb, rows, c) sketch + (nb, G, c) bits -> (values f32,
    residual int8), both (nb, G, c)."""
    if _want_pallas(cfg):
        return sketch_peel_pallas(sketch, bits, block_ids, cfg,
                                  interpret=not _on_tpu())
    return ref_ops.sketch_peel_ref(sketch, bits, block_ids, cfg)


def fused_wire_supported(cfg: CompressionConfig) -> bool:
    """Whether the fused wire-codec ops cover this geometry.

    The fused kernels pack the bitmap *per batch row*, so a pack word
    must never straddle two rows (``lanes % 32 == 0`` — true for the
    default 512 and every lane-aligned TPU geometry), and only the exact
    bitmap index is pack-fusable (Bloom needs a global scatter over all
    coordinates, inherently cross-block).
    """
    return cfg.index == "bitmap" and cfg.lanes % 32 == 0


def encode_pack_quantize(xb: jnp.ndarray, block_ids: jnp.ndarray,
                         cfg: CompressionConfig,
                         exponents: jnp.ndarray | None = None,
                         mantissa_bits: int | None = None):
    """Fused wire producer: (nb, G, c) values + (nb,) ids ->
    (sketch (nb, rows, c) f32|int32, words (nb, wpb) uint32,
    maxabs (nb,) f32).

    ONE pass over the gradient stream: sketch-encode, bitmap-pack and
    per-block max-magnitude (the fxp32 exponent ingredient) in a single
    grid pass, optionally shared-exponent int32 quantization too when
    per-block ``exponents`` + ``mantissa_bits`` are given (exponents are
    a collective product, so the aggregator usually quantizes the
    already-Γ-compressed sketch after its pmax instead).
    """
    if (exponents is None) != (mantissa_bits is None):
        raise ValueError("exponents and mantissa_bits must be given together")
    if not fused_wire_supported(cfg):
        raise ValueError(
            f"fused wire codec unsupported for index={cfg.index!r}, "
            f"lanes={cfg.lanes} (need bitmap and lanes % 32 == 0)")
    if _want_pallas(cfg):
        return encode_pack_quantize_pallas(
            xb, block_ids, cfg, exponents=exponents,
            mantissa_bits=mantissa_bits, interpret=not _on_tpu())
    return ref_ops.encode_pack_quantize_ref(
        xb, block_ids, cfg, exponents=exponents, mantissa_bits=mantissa_bits)


def dequant_peel_unpack(sketch: jnp.ndarray, words: jnp.ndarray,
                        block_ids: jnp.ndarray, cfg: CompressionConfig,
                        exponents: jnp.ndarray | None = None,
                        mantissa_bits: int | None = None):
    """Fused wire consumer: (nb, rows, c) sketch + (nb, wpb) packed
    words + (nb,) ids -> (values f32, residual int8), both (nb, G, c).

    ONE pass over the aggregated wire payload: bitmap-unpack, optional
    exponent-bitcast dequantization of the int32 fxp32 sketch, and the
    full peeling loop in a single grid pass.
    """
    if (exponents is None) != (mantissa_bits is None):
        raise ValueError("exponents and mantissa_bits must be given together")
    if not fused_wire_supported(cfg):
        raise ValueError(
            f"fused wire codec unsupported for index={cfg.index!r}, "
            f"lanes={cfg.lanes} (need bitmap and lanes % 32 == 0)")
    if _want_pallas(cfg):
        return dequant_peel_unpack_pallas(
            sketch, words, block_ids, cfg, exponents=exponents,
            mantissa_bits=mantissa_bits, interpret=not _on_tpu())
    return ref_ops.dequant_peel_unpack_ref(
        sketch, words, block_ids, cfg, exponents=exponents,
        mantissa_bits=mantissa_bits)


def wire_codec_passes(cfg: CompressionConfig, quantized: bool = False):
    """Analytic pass counts over the bucket stream per wire direction.

    Feeds `core/costmodel.py`'s codec-compute term and the roofline
    `--codec` report. "Pass" = one full read of the stream-sized
    operand: fused = 1 each way; composed = encode + pack (+ quantize)
    on the producer, unpack + peel (+ dequant) on the consumer.
    """
    if fused_wire_supported(cfg) and _want_pallas(cfg):
        return {"producer": 1, "consumer": 1}
    extra = 1 if quantized else 0
    return {"producer": 2 + extra, "consumer": 2 + extra}


def sketch_estimate(sketch: jnp.ndarray, block_ids: jnp.ndarray,
                    cfg: CompressionConfig) -> jnp.ndarray:
    """Median-of-3 Count-Sketch estimate for every coordinate,
    (nb, rows, c) -> (nb, G, c).

    The sketch-only lossy decode (ablation path). Reference-backed on
    every policy: it is off the training hot path, and the peel kernel
    already computes the same median in-kernel for its residue, so a
    dedicated Pallas estimate kernel would duplicate that code for no
    measured benefit.
    """
    return ref_ops.sketch_estimate_ref(sketch, block_ids, cfg)
