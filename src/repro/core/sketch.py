"""Block-local Count Sketch (paper §3.1 + §3.4) — pure-jnp reference.

Every function here operates on the block layout ``(nb, G, c)`` produced by
:mod:`repro.core.blocks`. The sketch for a block is ``(rows, c)``; batch
``i`` of a block contributes its ``c`` values to row ``h_j(i)`` for the
three hashes ``j``, rotated by ``rot_j(i, blk)`` lanes and multiplied by
the sign ``g_j(i)``:

    Y[h_j(i), (l + rot_j(i,blk)) % c] += g_j(i) * x[i, l]

Row tables and signs are compile-time constants shared across blocks; the
rotations vary per block (computed in-graph from the block id), which is
what makes each block an independent random 3-partite hypergraph.

Linearity of every step gives the homomorphic property:
``encode(sum_w X_w) == sum_w encode(X_w)`` exactly (up to fp addition
order), so sketches aggregate with a plain ``psum``.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .config import CompressionConfig
from . import hashing


def plan_tables(cfg: CompressionConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Static (rows, signs) tables: int32 (G, 3), float32 (G, 3)."""
    return (hashing.batch_rows(cfg.group, cfg.rows, cfg.seed),
            hashing.batch_signs(cfg.group, cfg.seed))


# ----------------------------------------------------------------------
# Lane rotations (the §3.4 locality randomisation)
# ----------------------------------------------------------------------

def _rolled_slices(ext: jnp.ndarray, starts: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """Per-row dynamic-slice out of a doubled row. ext (..., 2c), starts
    (...,) -> (..., c). Lowers to a gather with *scalar* per-row indices —
    O(1) index memory, unlike take_along_axis whose (…, c, ndim) index
    tensor costs 4x the payload."""
    def one(row, s):
        return jax.lax.dynamic_slice(row, (s,), (lanes,))
    f = one
    for _ in range(ext.ndim - 1):
        f = jax.vmap(f)
    return f(ext, starts)


def roll_to_sketch(x: jnp.ndarray, rot: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """Forward rotation: x (nb,G,c) -> (nb,G,3,c) where out[m] = x[(m-rot)%c]."""
    ext = jnp.concatenate([x, x], axis=-1)                 # (nb,G,2c)
    ext = jnp.broadcast_to(ext[:, :, None, :], ext.shape[:2] + (3, 2 * lanes))
    starts = (lanes - rot) % lanes                         # (nb,G,3)
    return _rolled_slices(ext, starts, lanes)


def roll_from_sketch(y: jnp.ndarray, rot: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """Inverse rotation: y (nb,G,3,c) -> (nb,G,3,c) where out[l] = y[(l+rot)%c]."""
    ext = jnp.concatenate([y, y], axis=-1)                 # (nb,G,3,2c)
    return _rolled_slices(ext, rot % lanes, lanes)


# ----------------------------------------------------------------------
# Scatter / gather between batches and sketch rows
# ----------------------------------------------------------------------

def row_members(rows_tbl: np.ndarray, rows: int):
    """Static scatter plan: for each sketch row ``r``, its hash ``j`` and
    the batches ``i`` with ``h_j(i) == r``, ascending. The row tables are
    3-partite, so every row is fed by exactly one hash."""
    per = rows // 3
    return [(r // per, [int(i) for i in np.nonzero(rows_tbl[:, r // per] == r)[0]])
            for r in range(rows)]


def scatter_rows(contrib: jnp.ndarray, rows_tbl: np.ndarray, rows: int) -> jnp.ndarray:
    """contrib (nb,G,3,c) -> sketch (nb,rows,c), summed on h_j(i).

    Each row is a left-to-right chain of adds over its batches in
    ascending order (:func:`row_members`) — the same order the Pallas
    kernels add in, so float sketches and peels agree bit for bit on any
    backend (a scatter-add leaves the order to the compiler)."""
    nb, _, _, c = contrib.shape
    out = []
    for j, members in row_members(rows_tbl, rows):
        acc = jnp.zeros((nb, c), contrib.dtype)
        for i in members:
            acc = acc + contrib[:, i, j, :]
        out.append(acc)
    return jnp.stack(out, axis=1)


def gather_rows(sketch: jnp.ndarray, rows_tbl: np.ndarray) -> jnp.ndarray:
    """sketch (nb,rows,c) -> (nb,G,3,c) gathered at h_j(i)."""
    nb, _, c = sketch.shape
    h_flat = jnp.asarray(rows_tbl.reshape(-1), dtype=jnp.int32)
    g3 = h_flat.shape[0]
    return sketch[:, h_flat, :].reshape(nb, g3 // 3, 3, c)


# ----------------------------------------------------------------------
# Encode / estimate
# ----------------------------------------------------------------------

def encode_blocks(xb: jnp.ndarray, block_ids: jnp.ndarray,
                  cfg: CompressionConfig) -> jnp.ndarray:
    """Count-Sketch encode: (nb,G,c) values -> (nb,rows,c) sketch (f32)."""
    rows_tbl, signs = plan_tables(cfg)
    rot = hashing.block_rotations(block_ids, cfg.group, cfg.lanes, cfg.seed)
    x = xb.astype(jnp.float32)
    contrib = roll_to_sketch(x, rot, cfg.lanes) * jnp.asarray(signs)[None, :, :, None]
    return scatter_rows(contrib, rows_tbl, cfg.rows)


def estimate_blocks(sketch: jnp.ndarray, block_ids: jnp.ndarray,
                    cfg: CompressionConfig) -> jnp.ndarray:
    """Unbiased median-of-3 Count-Sketch estimate for every coordinate.

    This is the paper's fallback for coordinates the peeling process cannot
    resolve (footnote 5) and the entire decoder of the sketch-only
    (Sketched-SGD-style) lossy baseline.
    """
    rows_tbl, signs = plan_tables(cfg)
    rot = hashing.block_rotations(block_ids, cfg.group, cfg.lanes, cfg.seed)
    y = gather_rows(sketch, rows_tbl)                       # (nb,G,3,c)
    y = roll_from_sketch(y, rot, cfg.lanes)
    est = y * jnp.asarray(signs)[None, :, :, None]
    v0, v1, v2 = est[:, :, 0], est[:, :, 1], est[:, :, 2]
    # median3 = sum - max - min
    return v0 + v1 + v2 - jnp.maximum(jnp.maximum(v0, v1), v2) \
        - jnp.minimum(jnp.minimum(v0, v1), v2)
