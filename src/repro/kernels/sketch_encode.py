"""Pallas TPU kernel: block-local Count-Sketch encode (paper §3.1 + §3.4).

Grid = one cell per *tile* of ``encode_block_tile`` sketch blocks. Each
cell loads its (B, G, c) tile of gradient batches and its (B, G, 3)
rotation offsets HBM→VMEM, then, block by block:

- rotates every batch row by its per-(block, batch, hash) offset (the
  §3.4 locality randomisation) with a barrel shifter: one static lane
  rotation per offset bit, kept or dropped per row by a select — pure
  data movement, so exact, and made only of ops Mosaic lowers;
- adds the rotated, signed rows onto their sketch rows in the static
  order of :func:`repro.core.sketch.row_members` — the order the jnp
  reference adds in, so the f32 sketch is bit-identical to it;
- writes the (rows, c) sketch block back.

The block-local hashing guarantees no other grid cell ever touches these
rows, which is how the paper's GPU scatter-with-atomics becomes a
race-free TPU kernel. Row targets and signs are compile-time constants
(the static hash plan); the rotations come from
:func:`repro.core.hashing.block_rotations`, the one implementation of the
hash stream, evaluated outside the kernel (180 int32 per block against
the block's 30,720 values at the default geometry).

Every block spec keeps the tile on the leading, untiled dim and whole
(G, c) / (rows, c) / (G, 3) planes in the last two dims, so any tile
size is legal for Mosaic. VMEM per cell at the defaults (B=8, G=60,
c=512, rows=6): x tile 960 KiB, sketch out 96 KiB, rotations
8*(64x128)*4 = 256 KiB as laid out in VMEM, one (G, c) f32 scratch plane
— each double-buffered, well under v5e's 16 MiB scoped VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.config import CompressionConfig
from repro.core import hashing
from repro.core.sketch import plan_tables, row_members


def roll_rows(v, rot_col, lanes: int, inverse: bool = False):
    """Rotate each row of ``v`` (G, c) by its own offset ``rot_col``
    (G, 1) int32 in [0, c): ``out[i, m] = v[i, (m - rot_i) % c]``
    (``inverse``: ``(m + rot_i) % c``), as ``jnp.roll`` per row.

    Barrel shifter: for each bit ``k`` of the offset, a static lane
    rotation by ``2**k`` taken where the row's bit is set."""
    k = 0
    while (1 << k) < lanes:
        s = 1 << k
        shift = (lanes - s) % lanes if inverse else s
        bit = ((rot_col >> k) & 1) == 1
        v = jnp.where(bit, pltpu.roll(v, shift, 1), v)
        k += 1
    return v


def block_rotations(block_ids: jnp.ndarray, cfg: CompressionConfig):
    """(nb,) ids -> (nb, G, 3) int32 rotation offsets, the kernels'
    per-block operand."""
    return hashing.block_rotations(jnp.asarray(block_ids, jnp.int32),
                                   cfg.group, cfg.lanes, cfg.seed)


def add_rows(plane_ref, members, sign_col, acc):
    """``acc`` (1, c) plus the rows ``members`` of ``plane_ref`` (G, c)
    times their static signs (``sign_col`` None: unsigned), in order —
    one row of :func:`repro.core.sketch.scatter_rows`."""
    for i in members:
        row = plane_ref[pl.ds(i, 1), :]
        if sign_col is not None:
            row = row * float(sign_col[i])
        acc = acc + row
    return acc


def encode_block(x, rot, cfg: CompressionConfig, plane_ref, out_row):
    """Encode one block: ``x`` (G, c) f32 + ``rot`` (G, 3) int32 ->
    ``out_row(r, row)`` called with each (1, c) sketch row.

    ``plane_ref`` is a (G, c) f32 VMEM scratch plane holding one hash's
    rotated batches while its rows are summed."""
    rows_tbl, signs = plan_tables(cfg)
    members = row_members(rows_tbl, cfg.rows)
    for j in range(3):
        plane_ref[...] = roll_rows(x, rot[:, j:j + 1], cfg.lanes)
        for r, (jr, mem) in enumerate(members):
            if jr != j:
                continue
            out_row(r, add_rows(plane_ref, mem, signs[:, j],
                                jnp.zeros((1, cfg.lanes), jnp.float32)))


def _encode_kernel(rot_ref, x_ref, o_ref, plane_ref, *,
                   cfg: CompressionConfig):
    def body(b, carry):
        def out_row(r, row):
            o_ref[b, pl.ds(r, 1), :] = row
        encode_block(x_ref[b].astype(jnp.float32), rot_ref[b], cfg,
                     plane_ref, out_row)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


def tile_geometry(nb: int, tile: int):
    """(tile, padded nb) for a grid of ``tile``-block cells."""
    tile = max(1, min(tile, nb))
    return tile, -(-nb // tile) * tile


def pad_blocks(a, padded: int):
    """Zero-pad the leading (block) dim of ``a`` to ``padded``."""
    nb = a.shape[0]
    if padded == nb:
        return a
    return jnp.pad(a, ((0, padded - nb),) + ((0, 0),) * (a.ndim - 1))


@compat.per_device
def sketch_encode_pallas(xb: jnp.ndarray, block_ids: jnp.ndarray,
                         cfg: CompressionConfig,
                         interpret: bool = True) -> jnp.ndarray:
    """(nb, G, c) values + (nb,) ids -> (nb, rows, c) sketch."""
    nb = xb.shape[0]
    tile, padded = tile_geometry(nb, cfg.encode_block_tile)
    G, c, R = cfg.group, cfg.lanes, cfg.rows
    # Zero blocks encode to zero sketches whatever their rotations;
    # sliced back off below.
    rot = pad_blocks(block_rotations(block_ids, cfg), padded)
    xb = pad_blocks(xb, padded)
    out = pl.pallas_call(
        functools.partial(_encode_kernel, cfg=cfg),
        grid=(padded // tile,),
        in_specs=[
            pl.BlockSpec((tile, G, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile, G, c), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, R, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, R, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((G, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(rot, xb)
    return out[:nb] if padded != nb else out
