"""Reduction-tree topologies for the in-network aggregation tier (PR 4).

The paper's deployment story puts aggregation *in the network*: workers
send their sketch+bitmap up a worker -> ToR -> spine tree once, switches
combine (integer add / OR) as the stream passes, and the root broadcasts
the aggregate back down. This module maps that tree onto the mesh axes
the repo already reduces over, and provides the collective analogue —
a reduce-to-root + broadcast schedule built from ``jax.lax.ppermute``
binary trees, one level per mesh axis.

Semantics are deliberately restricted to what a programmable switch can
do: :func:`tree_all_reduce` combines with **integer add or bitwise OR
only** and rejects float operands — the float sketch must go through the
fixed-point wire first (:mod:`repro.net.fixedpoint`). Because integer
adds and ORs are exactly associative/commutative, the tree result is
bit-identical to a flat ``psum`` / OR-AllReduce of the same operands,
which is also the wire taken with ``use_ppermute=False``.

Chunk/port ordering follows :func:`repro.core.collectives.linear_rank`:
worker *w*'s switch port is its rank-major linear index over the DP
axes, so the emulated :class:`repro.net.switch.SwitchModel` and the
in-mesh schedule agree on which payload arrives where.

Wire model (per direction; ``P`` = sketch+index payload bytes): every
worker sends ``P`` once up its access link and receives ``P`` once back
— against the ring AllReduce's ``2(W-1)/W * P`` per link. A level-``i``
switch ingests ``fanout_i * P`` across its child ports but forwards
only the aggregated ``P`` up, so the *root* link carries ``P`` per
direction no matter how many workers hang below it (``P/fanout`` per
child, amortized). :meth:`Topology.link_profile` reports these numbers;
:meth:`repro.core.config.CompressionConfig.strategy_wire_bytes` folds
them into the per-strategy accounting.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.collectives import _check_axis_indices, or_allreduce

from .fixedpoint import ceil_log2

TOPOLOGIES = ("flat", "tor_spine")


@dataclasses.dataclass(frozen=True)
class Topology:
    """A reduction tree mapped onto (manual) mesh axes.

    ``levels`` are the per-level mesh axes in leaf-to-root order
    (innermost axis first: workers under one ToR are ICI-near). The
    ppermute schedule is identical for every kind — the kind only
    changes how the physical tree is *accounted*: ``flat`` models one
    big switch with ``workers`` ports, ``tor_spine`` one switch tier
    per level.
    """

    kind: str
    levels: Tuple[str, ...]
    sizes: Tuple[int, ...]

    @property
    def workers(self) -> int:
        w = 1
        for s in self.sizes:
            w *= s
        return w

    @property
    def fanouts(self) -> Tuple[int, ...]:
        """Children per switch, leaf tier first."""
        if self.kind == "flat":
            return (self.workers,)
        return self.sizes

    @property
    def depth(self) -> int:
        return len(self.fanouts)

    def switches_per_level(self) -> Tuple[int, ...]:
        """How many switches each tier has (leaf tier first)."""
        out, below = [], 1
        for f in self.fanouts:
            below *= f
            out.append(self.workers // below)
        return tuple(out)

    def link_profile(self, payload_bytes: int) -> Dict[str, object]:
        """Per-direction byte loads of one aggregation round (see module
        docstring). ``switch_ingress_bytes`` is per switch, per tier."""
        if self.workers == 1:
            return {"worker_link_bytes": 0, "root_link_bytes": 0,
                    "switch_ingress_bytes": (0,) * self.depth}
        return {
            "worker_link_bytes": payload_bytes,
            "root_link_bytes": payload_bytes,
            "switch_ingress_bytes": tuple(
                f * payload_bytes for f in self.fanouts),
        }

    def window_profile(self, chunk_bytes: int, n_chunks: int,
                       slots: int) -> Dict[str, object]:
        """Per-window wire accounting of the *streamed* tree (PR 5).

        The collective schedule reduces the stream in windows of at most
        ``slots`` bucket chunks (``tree_all_reduce(...,
        window_slots=slots)``), exactly as the emulated
        :class:`repro.net.switch.SwitchModel` streams its bounded SRAM
        slot pool — this static profile and the switch's runtime
        ``report()`` must agree window for window (``windows``,
        ``occupancy_peak``, ``window_chunks``, ``window_root_bytes``,
        and the per-direction root-link total), which the tests pin.
        ``chunk_bytes``: wire bytes of one chunk (int32 sketch + uint32
        bitmap words for one bucket on the fxp32 wire).
        """
        if chunk_bytes < 0 or n_chunks < 0:
            raise ValueError("chunk_bytes/n_chunks must be >= 0")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        window_chunks = tuple(min(slots, n_chunks - w0)
                              for w0 in range(0, n_chunks, slots))
        return {
            "windows": len(window_chunks),
            "occupancy_peak": max(window_chunks, default=0),
            "window_chunks": window_chunks,
            "window_root_bytes": tuple(c * chunk_bytes
                                       for c in window_chunks),
            "root_link_bytes": n_chunks * chunk_bytes,
        }


def make_topology(kind: str, mesh, dp_axes: Sequence[str]) -> Topology:
    """Map ``kind`` onto the mesh's DP axes.

    ``flat``: one switch tier with all ``W`` workers as ports (any
    number of DP axes). ``tor_spine``: one tier per DP axis — needs at
    least two axes so there is a ToR level *and* a spine level; the
    innermost axis is the ToR fanout (ICI-near workers share a ToR), the
    outermost the spine fanout.
    """
    if isinstance(dp_axes, str):
        dp_axes = (dp_axes,)
    dp_axes = tuple(dp_axes)
    if kind not in TOPOLOGIES:
        raise ValueError(f"unknown topology {kind!r}; have {TOPOLOGIES}")
    if not dp_axes:
        raise ValueError("topology needs at least one DP axis")
    missing = [a for a in dp_axes if a not in mesh.shape]
    if missing:
        raise ValueError(f"mesh has no axes {missing}")
    if kind == "tor_spine" and len(dp_axes) < 2:
        raise ValueError(
            "topology='tor_spine' needs >= 2 DP axes (one for the ToR "
            f"tier, one for the spine), got {dp_axes}; use 'flat' for a "
            "single-axis mesh")
    levels = tuple(reversed(dp_axes))  # innermost (ICI-near) tier first
    return Topology(kind=kind, levels=levels,
                    sizes=tuple(mesh.shape[a] for a in levels))


# ----------------------------------------------------------------------
# ppermute tree schedules (manual collectives)
# ----------------------------------------------------------------------

def _combine_fn(combine: str, dtype):
    if combine == "add":
        if not jnp.issubdtype(dtype, jnp.integer):
            raise TypeError(
                "tree_all_reduce combines with integer adds only (switch "
                f"register semantics); got {dtype}. Quantize the sketch "
                "through repro.net.fixedpoint.FixedPointWire first.")
        return lambda a, b: a + b
    if combine == "or":
        if not jnp.issubdtype(dtype, jnp.unsignedinteger):
            raise TypeError(
                f"tree_all_reduce 'or' needs unsigned words, got {dtype}")
        return lambda a, b: a | b
    raise ValueError(f"combine must be 'add' or 'or', got {combine!r}")


def reduce_to_root(x: jnp.ndarray, axis_name: str, combine: str,
                   idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """Binary-tree reduction to rank 0 of ``axis_name``: ceil(log2 n)
    ppermute steps, child ``r + d`` sending its subtotal to ``r``.
    Non-root ranks end with stale partials (a broadcast overwrites
    them). Works for any axis size, power of two or not.

    ``idx`` is accepted for signature symmetry with the broadcast (the
    reduction itself needs no rank test: a rank not targeted by a step
    receives zeros, the identity of both combiners).
    """
    del idx
    n = jax.lax.axis_size(axis_name)
    comb = _combine_fn(combine, x.dtype)
    d = 1
    while d < n:
        pairs = [(i, i - d) for i in range(d, n, 2 * d)]
        x = comb(x, jax.lax.ppermute(x, axis_name, pairs))
        d *= 2
    return x


def broadcast_from_root(x: jnp.ndarray, axis_name: str,
                        idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """Inverse tree: rank 0's value reaches every rank of ``axis_name``
    in ceil(log2 n) ppermute steps. ``idx``: this shard's index on the
    axis — pass it when calling from a nested region (see
    :func:`repro.core.collectives.or_allreduce_ring`)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    if idx is None:
        idx = jax.lax.axis_index(axis_name)
    d = 1 << (ceil_log2(n) - 1)
    while d >= 1:
        pairs = [(i - d, i) for i in range(d, n, 2 * d)]
        recv = jax.lax.ppermute(x, axis_name, pairs)
        x = jnp.where(idx % (2 * d) == d, recv, x)
        d //= 2
    return x


def tree_all_reduce(x: jnp.ndarray, topo: Topology, combine: str,
                    axis_indices: Optional[dict] = None,
                    use_ppermute: bool = True,
                    window_slots: Optional[int] = None) -> jnp.ndarray:
    """Reduce-to-root + broadcast over the topology's levels.

    The in-mesh analogue of in-network aggregation: each level's axis is
    reduced to its rank-0 "switch", the root holds the full aggregate,
    and the broadcast pushes it back down the same tree. ``combine`` is
    ``"add"`` (integer) or ``"or"`` (uint32) — float operands raise (a
    switch cannot sum floats; see :mod:`repro.net.fixedpoint`).

    Because both combiners are exact, the result is bit-identical to the
    flat collective over the same axes — which is what
    ``use_ppermute=False`` runs instead of the ppermute tree.

    ``window_slots`` is the windowed mode (PR 5): the leading dim of
    ``x`` is a stream of chunks (e.g. buckets) and the tree reduces at
    most ``window_slots`` of them per round, window by window, exactly
    as a real switch streams its bounded SRAM slot pool
    (:class:`repro.net.switch.SwitchModel`; per-window traffic in
    :meth:`Topology.window_profile`). Bit-identical to the one-shot
    reduction — windowing only splits the schedule.

    ``axis_indices``: {axis: this shard's index} — required complete (or
    None), as in :func:`repro.core.collectives.or_allreduce`.
    """
    _check_axis_indices(topo.levels, axis_indices)
    if combine not in ("add", "or"):
        raise ValueError(f"combine must be 'add' or 'or', got {combine!r}")
    _combine_fn(combine, x.dtype)  # dtype gate even on the fallback wire
    if window_slots is not None:
        if window_slots < 1:
            raise ValueError(
                f"window_slots must be >= 1, got {window_slots}")
        n = x.shape[0]
        if n > window_slots:
            parts = [
                tree_all_reduce(x[w0:w0 + window_slots], topo, combine,
                                axis_indices=axis_indices,
                                use_ppermute=use_ppermute)
                for w0 in range(0, n, window_slots)]
            return jnp.concatenate(parts, axis=0)
    if not use_ppermute:
        if combine == "add":
            return jax.lax.psum(x, tuple(topo.levels))
        # or_allreduce reduces its axis tuple innermost-first; levels are
        # already innermost-first, so hand it the reversed (outer-first)
        # spelling it expects.
        return or_allreduce(x, tuple(reversed(topo.levels)),
                            axis_indices=axis_indices)
    for ax in topo.levels:
        idx = axis_indices[ax] if axis_indices else None
        x = reduce_to_root(x, ax, combine, idx=idx)
    for ax in reversed(topo.levels):
        idx = axis_indices[ax] if axis_indices else None
        x = broadcast_from_root(x, ax, idx=idx)
    return x
