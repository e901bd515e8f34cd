"""The wire's codec written out from its definition: block Count Sketch,
bitmap index, parallel peeling with a median fallback.

The gradient stream (every leaf flattened, concatenated in the parameter
tree's order, zero-padded) is cut into blocks of ``G * c`` elements,
``G = round(rows / ratio)`` batches of ``c = lanes`` lanes. Batch ``i`` of
block ``b`` adds ``g_j(i) * x[i, l]`` to sketch row ``h_j(i)`` at lane
``(l + rot_j(b, i)) mod c`` for each of three hashes ``j``. ``h``, ``g``
and ``rot`` come from the murmur3 finalizer with the configuration's
``seed``. The receiver knows which coordinates are non-zero (the OR of
the senders' bitmaps) and peels: each round, every indexed coordinate
that owns a cell no other indexed coordinate reaches takes that cell's
value (the first such hash), and all peeled coordinates leave their
cells together. After ``rounds`` rounds, the coordinates left take the
median of their three signed cells.

Lane rotations are barrel shifters of static rolls, and the rows are
summed by static index lists, so the whole decode is elementwise work
that a TPU runs at memory speed.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

M1, M2 = 0x85EBCA6B, 0xC2B2AE35
U32 = 0xFFFFFFFF


def _mix_np(x):
    x = np.asarray(x, np.uint64) & U32
    x ^= x >> 16
    x = (x * M1) & U32
    x ^= x >> 13
    x = (x * M2) & U32
    x ^= x >> 16
    return x


def _mix(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(M2)
    return x ^ (x >> 16)


class Geometry:
    def __init__(self, c):
        self.rows, self.lanes, self.rounds = c["rows"], c["lanes"], c["rounds"]
        self.seed = c["seed"]
        self.group = max(1, round(self.rows / c["ratio"]))
        self.block = self.group * self.lanes
        per = self.rows // 3
        i = np.arange(self.group, dtype=np.uint64)
        salt_h = (self.seed * 0x9E3779B9) & U32
        salt_g = (self.seed ^ 0xA5A5A5A5) & U32
        self.row = np.stack([(_mix_np(i * 3 + j + salt_h) % per) + j * per
                             for j in range(3)], 1).astype(np.int32)
        self.sign = np.stack([np.where(_mix_np(i * 3 + j + salt_g) & 1, 1.0, -1.0)
                              for j in range(3)], 1).astype(np.float32)
        # batches feeding each sketch row (every row belongs to one hash)
        self.members = [(r // per, np.nonzero(self.row[:, r // per] == r)[0])
                        for r in range(self.rows)]

    def rotations(self, ids):
        """(nb,) int32 block ids -> (nb, G, 3) lane rotations."""
        i = jnp.arange(self.group, dtype=jnp.uint32)[None, :, None]
        j = jnp.arange(3, dtype=jnp.uint32)[None, None, :]
        key = (ids.astype(jnp.uint32)[:, None, None] * jnp.uint32(0x01000193)
               + i * jnp.uint32(3) + j
               + jnp.uint32(self.seed * 2654435761 & U32))
        return (_mix(key) % jnp.uint32(self.lanes)).astype(jnp.int32)


def _roll_left(x, r, lanes):
    """out[..., l] = x[..., (l + r) mod lanes], r broadcast per row."""
    k = 1
    while k < lanes:
        moved = jnp.concatenate([x[..., k:], x[..., :k]], axis=-1)
        x = jnp.where(((r >> (k.bit_length() - 1)) & 1)[..., None] == 1, moved, x)
        k *= 2
    return x


def _at_cells(y, geo, rot, j):
    """Sketch (nb, rows, c) -> (nb, G, c): the cell hash j of each
    coordinate lands in."""
    return _roll_left(y[:, geo.row[:, j], :], rot[:, :, j], geo.lanes)


def _to_rows(contrib, geo, rot):
    """Per-coordinate contributions (3 x (nb, G, c)) -> sketch rows."""
    rolled = [_roll_left(contrib[j], (geo.lanes - rot[:, :, j]) % geo.lanes,
                         geo.lanes) for j in range(3)]
    return jnp.stack([rolled[j][:, mem, :].sum(axis=1)
                      for j, mem in geo.members], axis=1)


def encode(x, ids, geo):
    """(nb, G, c) values -> (nb, rows, c) sketch."""
    rot = geo.rotations(ids)
    sg = jnp.asarray(geo.sign)
    return _to_rows([x * sg[None, :, j, None] for j in range(3)], geo, rot)


def decode(sketch, bits, ids, geo):
    """Aggregated sketch (nb, rows, c) and index (nb, G, c) bool -> values
    (nb, G, c) and the unpeeled mask."""
    rot = geo.rotations(ids)
    sg = jnp.asarray(geo.sign)
    deg = _to_rows([bits.astype(jnp.float32)] * 3, geo, rot)

    def round_(_, st):
        y, d, b, x = st
        val = jnp.zeros(x.shape, jnp.float32)
        done = jnp.zeros(b.shape, bool)
        for j in range(3):
            pure = b & ~done & (_at_cells(d, geo, rot, j) == 1.0)
            val = jnp.where(pure, sg[None, :, j, None] * _at_cells(y, geo, rot, j), val)
            done = done | pure
        y = y - _to_rows([val * sg[None, :, j, None] for j in range(3)], geo, rot)
        d = d - _to_rows([done.astype(jnp.float32)] * 3, geo, rot)
        return y, d, b & ~done, x + val

    y, _, b, x = jax.lax.fori_loop(
        0, geo.rounds, round_,
        (sketch, deg, bits, jnp.zeros(bits.shape, jnp.float32)))
    est = [sg[None, :, j, None] * _at_cells(y, geo, rot, j) for j in range(3)]
    med = (est[0] + est[1] + est[2] - jnp.maximum(jnp.maximum(est[0], est[1]), est[2])
           - jnp.minimum(jnp.minimum(est[0], est[1]), est[2]))
    return x + jnp.where(b, med, 0.0), b
