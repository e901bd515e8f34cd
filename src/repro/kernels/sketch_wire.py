"""Fused Pallas wire-codec kernels: one VMEM pass per wire direction (PR 7).

ROADMAP open item 3 (fusion half): the compressed hot path used to make
separate passes over the bucket stream — sketch-encode, then bitmap-pack,
then (fxp32) quantize on the send side; dequant, peel, residual-unpack on
the receive side. Each pass re-reads the stream from HBM, and at the
smoke-benchmark sizes that codec compute — not link bytes — dominates
wall time (the regime "On the Utility of Gradient Compression" warns
about, and the one THC's low-overhead codec discipline targets).

This module fuses each trio into ONE `pallas_call` grid pass:

- **producer** (`encode_pack_quantize_pallas`): gradient blocks HBM→VMEM
  once; each block runs the shared :func:`encode_block`, packs its
  non-zero bitmap into 32-bit words *in VMEM*, reduces the per-block max
  magnitude (the fxp32 exponent ingredient — a free byproduct of the
  block already being resident), and optionally applies the
  shared-exponent int32 quantization before the sketch ever reaches
  HBM. Wire payload out, gradients in, one pass.
- **consumer** (`dequant_peel_unpack_pallas`): wire payload HBM→VMEM
  once; each block unpacks its bitmap words, optionally dequantizes the
  int32 sketch by exponent-field bitcast (:func:`repro.net.fixedpoint.pow2`
  — exact powers of two, never `exp2`), and runs the shared
  :func:`peel_block` loop to recovered values + int8 residual.

Both kernels *reuse the exact block cores* of the unfused kernels
(`encode_block` / `peel_block`) and the exact word ordering of
`core/index.pack_bits`, so bit-for-bit parity with the composed path is
structural: there is one implementation of the math, fused and unfused
paths differ only in how many times the stream crosses HBM.

Packing constraint: the bitmap is packed per batch row, so a pack word
must not straddle two rows — ``lanes % 32 == 0``
(`repro.kernels.ops.fused_wire_supported`); the ops layer falls back to
the composed reference otherwise. In the kernel a row's words are a
(G, c/32) int32 plane, reshaped and bitcast to the flat uint32 wire
words outside. Per-block fxp32 exponents ride in SMEM, one scalar per
block.

The fxp32 quantize leg takes *precomputed* exponents: deriving shared
exponents needs a cross-worker `pmax`, a collective that cannot live
inside a single-device kernel. The aggregator therefore runs the
producer unquantized (emitting `maxabs`), pmaxes the 4 B/bucket exponent
metadata, then quantizes the (stream-size/Γ) sketch — the *bucket
stream* is still read exactly once. The quantized producer leg exists
for known-exponent callers and parity tests; the dequant consumer leg is
always fused (exponents ride the wire).

VMEM adds over the unfused kernels are small: the words tile is
`B * G * c/32 * 4` bytes (1/32 of the x tile, lane-padded in VMEM) and
the maxabs tile `B` padded (1, 1) cells; budgets stay as documented in
`sketch_encode.py` / `sketch_peel.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.config import CompressionConfig
from repro.net.fixedpoint import pow2
from .sketch_encode import (block_rotations, encode_block, pad_blocks,
                            tile_geometry)
from .sketch_peel import peel_block, peel_scratch, plan_operands


def _lane(cfg: CompressionConfig):
    return jax.lax.broadcasted_iota(jnp.int32, (cfg.group, cfg.lanes), 1)


def _pack_block_bits(x, w_ref, b, cfg: CompressionConfig):
    """(G, c) values -> ``w_ref[b]`` (G, c/32) int32 packed non-zero
    bitmap.

    Bit order matches :func:`repro.core.index.pack_bits` on the
    flattened block exactly: word ``(i, q)`` bit ``k`` covers element
    ``(i, 32q + k)``, i.e. flat element ``w * 32 + k`` of word ``w = i *
    c/32 + q`` (requires ``c % 32 == 0``). Each word is the lane sum of
    its 32 distinct bit values — an exact OR in int32."""
    lane = _lane(cfg)
    v = jnp.where(x != 0, jnp.left_shift(1, lane % 32), 0).astype(jnp.int32)
    seg = lane // 32
    for q in range(cfg.lanes // 32):
        w_ref[b, :, pl.ds(q, 1)] = jnp.sum(jnp.where(seg == q, v, 0),
                                           axis=1, keepdims=True)


def _unpack_block_bits(w_ref, b, cfg: CompressionConfig):
    """``w_ref[b]`` (G, c/32) int32 words -> (G, c) int32 0/1 — inverse
    of :func:`_pack_block_bits`."""
    lane = _lane(cfg)
    seg = lane // 32
    shape = (cfg.group, cfg.lanes)
    rep = jnp.broadcast_to(w_ref[b, :, pl.ds(0, 1)], shape)
    for q in range(1, cfg.lanes // 32):
        rep = jnp.where(seg == q,
                        jnp.broadcast_to(w_ref[b, :, pl.ds(q, 1)], shape), rep)
    return jax.lax.shift_right_logical(rep, lane % 32) & 1


def _lane_pow2(k, cfg: CompressionConfig):
    """Scalar int32 ``k`` -> (1, c) f32 ``2**k`` (the exponent-field
    bitcast runs on the vector unit)."""
    return pow2(jnp.full((1, cfg.lanes), k, jnp.int32))


def _block_maxabs(sk):
    """(rows, c) -> (1, 1) max magnitude."""
    return jnp.max(jnp.max(jnp.abs(sk), axis=1, keepdims=True), axis=0,
                   keepdims=True)


def _wire_encode_kernel(rot_ref, x_ref, *refs, cfg: CompressionConfig,
                        mantissa_bits):
    if mantissa_bits is None:
        sk_ref, w_ref, mx_ref, plane_ref, acc_ref = refs
        exp_ref = None
    else:
        exp_ref, sk_ref, w_ref, mx_ref, plane_ref, acc_ref = refs

    def body(b, carry):
        x = x_ref[b].astype(jnp.float32)

        def out_row(r, row):
            acc_ref[pl.ds(r, 1), :] = row
        encode_block(x, rot_ref[b], cfg, plane_ref, out_row)
        acc = acc_ref[...]
        _pack_block_bits(x, w_ref, b, cfg)
        mx_ref[b] = _block_maxabs(acc)
        if exp_ref is None:
            sk_ref[b] = acc
        else:
            scale = _lane_pow2(mantissa_bits - exp_ref[0, 0, b], cfg)
            sk_ref[b] = jnp.rint(acc * scale).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


def _wire_peel_kernel(rot_ref, tbl_ref, sgn_ref, y_ref, w_ref, *refs,
                      cfg: CompressionConfig, mantissa_bits):
    if mantissa_bits is None:
        xo_ref, ro_ref, *scratch = refs
        exp_ref = None
    else:
        exp_ref, xo_ref, ro_ref, *scratch = refs

    def body(b, carry):
        bits = _unpack_block_bits(w_ref, b, cfg)
        y = y_ref[b].astype(jnp.float32)
        if exp_ref is not None:
            y = y * _lane_pow2(exp_ref[0, 0, b] - mantissa_bits, cfg)
        values, residual = peel_block(y, bits, rot_ref[b], tbl_ref, sgn_ref,
                                      cfg, scratch)
        xo_ref[b] = values
        ro_ref[b] = residual.astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0], body, 0)


def _exponent_operand(exponents, padded: int, tile: int):
    """(nb,) per-block int32 exponents -> (operand, SMEM block spec):
    one row of ``tile`` scalars per grid cell, read on the scalar unit."""
    spec = pl.BlockSpec((1, 1, tile), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    op = pad_blocks(jnp.asarray(exponents, jnp.int32), padded)
    return op.reshape(padded // tile, 1, tile), spec


@compat.per_device
def encode_pack_quantize_pallas(xb: jnp.ndarray, block_ids: jnp.ndarray,
                                cfg: CompressionConfig,
                                exponents: jnp.ndarray | None = None,
                                mantissa_bits: int | None = None,
                                interpret: bool = True):
    """Fused producer: (nb, G, c) values + (nb,) ids ->
    (sketch (nb, rows, c) f32|int32, words (nb, wpb) uint32,
    maxabs (nb,) f32) in one grid pass.

    With ``exponents`` (per-block int32) + ``mantissa_bits`` the sketch
    leaves the kernel fxp32-quantized; ``maxabs`` is always the
    *pre-quantize* f32 per-block max (the exponent ingredient).
    """
    nb = xb.shape[0]
    quantize = exponents is not None
    G, c, R = cfg.group, cfg.lanes, cfg.rows
    wpr = c // 32
    tile, padded = tile_geometry(nb, cfg.encode_block_tile)
    cell = lambda i: (i, 0, 0)
    in_specs = [pl.BlockSpec((tile, G, 3), cell),
                pl.BlockSpec((tile, G, c), cell)]
    operands = [pad_blocks(block_rotations(block_ids, cfg), padded),
                pad_blocks(xb, padded)]
    if quantize:
        # Padding exponent 0 only scales padded all-zero blocks: harmless.
        op, spec = _exponent_operand(exponents, padded, tile)
        in_specs.append(spec)
        operands.append(op)
    sk, words, mx = pl.pallas_call(
        functools.partial(_wire_encode_kernel, cfg=cfg,
                          mantissa_bits=(int(mantissa_bits) if quantize
                                         else None)),
        grid=(padded // tile,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((tile, R, c), cell),
                   pl.BlockSpec((tile, G, wpr), cell),
                   pl.BlockSpec((tile, 1, 1), cell)],
        out_shape=[
            jax.ShapeDtypeStruct((padded, R, c),
                                 jnp.int32 if quantize else jnp.float32),
            jax.ShapeDtypeStruct((padded, G, wpr), jnp.int32),
            jax.ShapeDtypeStruct((padded, 1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((G, c), jnp.float32),
                        pltpu.VMEM((R, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    words = jax.lax.bitcast_convert_type(words, jnp.uint32)
    return (sk[:nb], words[:nb].reshape(nb, G * wpr), mx[:nb, 0, 0])


@compat.per_device
def dequant_peel_unpack_pallas(sketch: jnp.ndarray, words: jnp.ndarray,
                               block_ids: jnp.ndarray,
                               cfg: CompressionConfig,
                               exponents: jnp.ndarray | None = None,
                               mantissa_bits: int | None = None,
                               interpret: bool = True):
    """Fused consumer: (nb, rows, c) sketch + (nb, wpb) uint32 words +
    (nb,) ids -> (values (nb, G, c) f32, residual (nb, G, c) int8) in
    one grid pass. With ``exponents`` + ``mantissa_bits`` the int32
    sketch is dequantized in-kernel before peeling.
    """
    nb = sketch.shape[0]
    dequant = exponents is not None
    G, c, R = cfg.group, cfg.lanes, cfg.rows
    wpr = c // 32
    tile, padded = tile_geometry(nb, cfg.peel_block_tile)
    cell = lambda i: (i, 0, 0)
    words = jax.lax.bitcast_convert_type(words, jnp.int32).reshape(nb, G, wpr)
    plan, plan_specs = plan_operands(cfg)
    in_specs = [pl.BlockSpec((tile, G, 3), cell), *plan_specs,
                pl.BlockSpec((tile, R, c), cell),
                pl.BlockSpec((tile, G, wpr), cell)]
    operands = [pad_blocks(block_rotations(block_ids, cfg), padded), *plan,
                pad_blocks(sketch, padded), pad_blocks(words, padded)]
    if dequant:
        op, spec = _exponent_operand(exponents, padded, tile)
        in_specs.append(spec)
        operands.append(op)
    plane = pl.BlockSpec((tile, G, c), cell)
    out = pl.pallas_call(
        functools.partial(_wire_peel_kernel, cfg=cfg,
                          mantissa_bits=(int(mantissa_bits) if dequant
                                         else None)),
        grid=(padded // tile,),
        in_specs=in_specs,
        out_specs=[plane, plane],
        out_shape=[jax.ShapeDtypeStruct((padded, G, c), jnp.float32),
                   jax.ShapeDtypeStruct((padded, G, c), jnp.int8)],
        scratch_shapes=peel_scratch(cfg),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*operands)
    return tuple(o[:nb] for o in out)
