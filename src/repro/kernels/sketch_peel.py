"""Pallas TPU kernel: parallel-peeling recovery (paper §3.2).

Grid = one cell per *tile* of ``peel_block_tile`` sketch blocks (the same
tiling as the encode kernel); the whole peeling loop for each block runs
*inside* the kernel on VMEM-resident scratch planes — the TPU translation
of the paper's §3.4 cache-locality argument (their GPU version re-reads
global memory per round; here HBM sees exactly one read of [Y, B] and one
write of X).

The round count is a static bound: with block-local sketches the paper's
peeling finishes in O(1) rounds, so a fixed ``cfg.rounds`` loses nothing
while keeping the kernel free of data-dependent control flow. Rounds
after the fixpoint are exact no-ops (all-false peel masks).

Per-round math is that of :mod:`repro.core.peeling` (the oracle), in
forms Mosaic lowers and in the oracle's order of float operations, so
the results are bit-identical:

- degree/value gather at ``h_j(i)``: a select among the (static) rows of
  hash ``j`` — the row tables are 3-partite, so hash ``j`` only ever
  reads its own ``rows/3`` rows;
- lane rotations: the barrel shifter of
  :func:`repro.kernels.sketch_encode.roll_rows`;
- first peelable hash (the oracle's argmax over 3): compare and select;
- scatter back onto the rows: chains of row adds in the static order of
  :func:`repro.core.sketch.row_members`.

VMEM per cell at the defaults (B=8, G=60, c=512, rows=6): sketch tile
96 KiB, int8 bits tile and int8 residual tile 240 KiB each, values out
960 KiB, rotations 256 KiB as laid out — each double-buffered — plus six
(G, c) / (rows, c) scratch planes, about 0.5 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.config import CompressionConfig
from repro.core.sketch import plan_tables, row_members
from .sketch_encode import (add_rows, block_rotations, pad_blocks,
                            roll_rows, tile_geometry)


def _gather_rows(ref, hcol, j: int, cfg: CompressionConfig):
    """(rows, c) ``ref`` -> (G, c): row ``h_j(i)`` for every batch ``i``,
    selected among hash ``j``'s rows by the (G, 1) row-table column."""
    per = cfg.rows // 3
    shape = (cfg.group, cfg.lanes)
    rows = list(range(j * per, (j + 1) * per))
    out = jnp.broadcast_to(ref[pl.ds(rows[-1], 1), :], shape)
    for r in reversed(rows[:-1]):
        out = jnp.where(hcol == r,
                        jnp.broadcast_to(ref[pl.ds(r, 1), :], shape), out)
    return out


def _scatter(dst_ref, plane_ref, members, signs, j: int, zero,
             subtract: bool):
    """Every row ``r`` of hash ``j``: ``dst[r] = s`` (or ``dst[r] - s``
    with ``subtract``) for ``s = sum_i sign * plane[i]`` —
    :func:`repro.core.sketch.scatter_rows` of that row."""
    for r, (jr, mem) in enumerate(members):
        if jr == j:
            acc = add_rows(plane_ref, mem, signs, zero)
            if subtract:
                acc = dst_ref[pl.ds(r, 1), :] - acc
            dst_ref[pl.ds(r, 1), :] = acc


def peel_block(y, bits, rot, tbl_ref, sgn_ref, cfg: CompressionConfig,
               scratch):
    """Peel one block: ``y`` (rows, c) f32 sketch, ``bits`` (G, c) int32
    0/1 index, ``rot`` (G, 3) int32 rotations -> (values (G, c) f32,
    residual (G, c) int32 0/1).

    ``tbl_ref`` / ``sgn_ref``: the (G, 3) row table (int32) and signs
    (f32). ``scratch``: the (y, d, b, x, f32 plane, int32 plane) VMEM
    scratch refs, (rows, c) for y/d and (G, c) for the rest."""
    y_s, d_s, b_s, x_s, pf, pi = scratch
    G, c = cfg.group, cfg.lanes
    rows_tbl, signs = plan_tables(cfg)
    members = row_members(rows_tbl, cfg.rows)
    zf = jnp.zeros((1, c), jnp.float32)
    zi = jnp.zeros((1, c), jnp.int32)
    cols = [(tbl_ref[:, j:j + 1], sgn_ref[:, j:j + 1], rot[:, j:j + 1])
            for j in range(3)]

    # Initial degrees: scatter the rotated index bits.
    y_s[...] = y
    b_s[...] = bits
    x_s[...] = jnp.zeros((G, c), jnp.float32)
    for j, (_, _, rcol) in enumerate(cols):
        pi[...] = roll_rows(bits, rcol, c)
        _scatter(d_s, pi, members, None, j, zi, subtract=False)

    def at(j):
        """(degree, signed value) of every batch's cell for hash j."""
        hcol, scol, rcol = cols[j]
        d_at = roll_rows(_gather_rows(d_s, hcol, j, cfg), rcol, c,
                         inverse=True)
        v_at = roll_rows(_gather_rows(y_s, hcol, j, cfg), rcol, c,
                         inverse=True) * scol
        return d_at, v_at

    def round_body(_, carry):
        live = b_s[...] != 0
        (d0, v0), (d1, v1), (d2, v2) = at(0), at(1), at(2)
        p0 = (d0 == 1) & live
        p1 = (d1 == 1) & live
        p2 = (d2 == 1) & live
        any_peel = p0 | p1 | p2
        val = jnp.where(p0, v0, jnp.where(p1, v1, v2))
        val = jnp.where(any_peel, val, 0.0)
        ones = jnp.where(any_peel, 1, 0).astype(jnp.int32)
        for j, (_, _, rcol) in enumerate(cols):
            pf[...] = roll_rows(val, rcol, c)
            _scatter(y_s, pf, members, signs[:, j], j, zf, subtract=True)
            pi[...] = roll_rows(ones, rcol, c)
            _scatter(d_s, pi, members, None, j, zi, subtract=True)
        b_s[...] = jnp.where(any_peel, 0, b_s[...])
        x_s[...] = x_s[...] + val
        return carry

    jax.lax.fori_loop(0, cfg.rounds, round_body, 0)

    # Residue -> unbiased median-of-3 estimate (paper footnote 5).
    v0, v1, v2 = at(0)[1], at(1)[1], at(2)[1]
    med = (v0 + v1 + v2
           - jnp.maximum(jnp.maximum(v0, v1), v2)
           - jnp.minimum(jnp.minimum(v0, v1), v2))
    b = b_s[...]
    return x_s[...] + jnp.where(b != 0, med, 0.0), b


def peel_scratch(cfg: CompressionConfig):
    """The VMEM scratch planes :func:`peel_block` works in."""
    G, c, R = cfg.group, cfg.lanes, cfg.rows
    return [pltpu.VMEM((R, c), jnp.float32), pltpu.VMEM((R, c), jnp.int32),
            pltpu.VMEM((G, c), jnp.int32), pltpu.VMEM((G, c), jnp.float32),
            pltpu.VMEM((G, c), jnp.float32), pltpu.VMEM((G, c), jnp.int32)]


def plan_operands(cfg: CompressionConfig):
    """(row table int32 (G, 3), signs f32 (G, 3)) kernel operands and
    their whole-array block specs."""
    rows_tbl, signs = plan_tables(cfg)
    spec = pl.BlockSpec((cfg.group, 3), lambda i: (0, 0))
    return ([jnp.asarray(rows_tbl, jnp.int32), jnp.asarray(signs)],
            [spec, spec])


def _peel_kernel(rot_ref, tbl_ref, sgn_ref, y_ref, b_ref, xo_ref, ro_ref,
                 *scratch, cfg: CompressionConfig):
    def body(b, carry):
        bits = jnp.minimum(jnp.abs(b_ref[b].astype(jnp.int32)), 1)
        values, residual = peel_block(y_ref[b].astype(jnp.float32), bits,
                                      rot_ref[b], tbl_ref, sgn_ref, cfg,
                                      scratch)
        xo_ref[b] = values
        ro_ref[b] = residual.astype(jnp.int8)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[0], body, 0)


@compat.per_device
def sketch_peel_pallas(sketch: jnp.ndarray, bits: jnp.ndarray,
                       block_ids: jnp.ndarray, cfg: CompressionConfig,
                       interpret: bool = True):
    """(nb,rows,c) sketch + (nb,G,c) bits -> (values (nb,G,c) f32,
    residual (nb,G,c) int8)."""
    nb = sketch.shape[0]
    tile, padded = tile_geometry(nb, cfg.peel_block_tile)
    G, c, R = cfg.group, cfg.lanes, cfg.rows
    # Zero sketch blocks with empty indexes peel to exact zeros; sliced
    # back off below.
    rot = pad_blocks(block_rotations(block_ids, cfg), padded)
    sketch = pad_blocks(sketch, padded)
    bits = pad_blocks(bits.astype(jnp.int8), padded)
    plan, plan_specs = plan_operands(cfg)
    plane = pl.BlockSpec((tile, G, c), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_peel_kernel, cfg=cfg),
        grid=(padded // tile,),
        in_specs=[pl.BlockSpec((tile, G, 3), lambda i: (i, 0, 0)),
                  *plan_specs,
                  pl.BlockSpec((tile, R, c), lambda i: (i, 0, 0)),
                  plane],
        out_specs=[plane, plane],
        out_shape=[jax.ShapeDtypeStruct((padded, G, c), jnp.float32),
                   jax.ShapeDtypeStruct((padded, G, c), jnp.int8)],
        scratch_shapes=peel_scratch(cfg),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(rot, *plan, sketch, bits)
    if padded != nb:
        out = [o[:nb] for o in out]
    return tuple(out)
