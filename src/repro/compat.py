"""Mesh and manual-region helpers shared by every module that builds a
mesh or opens a ``shard_map``.

The repo requires JAX >= 0.9 (``jax.shard_map`` with ``axis_names`` /
``check_vma``, ``jax.make_mesh`` with ``axis_types``). This module pins
the repo's conventions on top of those APIs in one place:

- every mesh axis is ``Auto`` (:func:`make_mesh`);
- a ``shard_map`` opened *inside* another manual region (the nested
  TP-local pack/unpack regions of :mod:`repro.core.aggregators`, inside
  the train step's DP-manual region) is opened against the context's
  abstract mesh, whose axis types record which axes are already manual
  — JAX rejects the concrete, all-``Auto`` mesh there
  (:func:`shard_map`);
- a Pallas kernel called where mesh axes are still auto runs per device
  (:func:`per_device`), and a caller that may choose a kernel asks how
  many devices those axes span (:func:`auto_devices`).
"""

from __future__ import annotations

import functools
import math
from typing import Iterable, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

from repro.parallel import hints


def full_manual_region(manual_axes, mesh) -> bool:
    """True when ``manual_axes`` covers every mesh axis.

    A full-manual region has no auto axes left for Shardy to manage, so
    a manual-axis ``all_gather`` there does not un-shard auto TP axes
    around it (the reason the ZeRO-1 gather in train/step.py otherwise
    uses zero-pad + psum at 2x the wire cost).
    """
    return set(mesh.axis_names) <= set(manual_axes)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with every axis in Auto mode."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(axis_shapes)),
        **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Iterable[str]] = None,
              check_vma: bool = False):
    """``jax.shard_map`` that also nests inside a manual region.

    Args:
      f:          function to map.
      mesh:       the device mesh. Inside an enclosing manual region the
                  context's abstract mesh (same axes, the enclosing
                  region's axes marked Manual) is used instead.
      in_specs/out_specs: as in jax.shard_map.
      axis_names: the axes to take *manual*. ``None`` means all of them.
      check_vma:  replication checking, as in jax.shard_map.
    """
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty and ctx.manual_axes:
        mesh = ctx
    manual = set(mesh.axis_names) if axis_names is None else set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check_vma)


def _auto_axes(ctx) -> set:
    """The axes of the context mesh ``ctx`` not taken manual."""
    return set() if ctx.empty else set(ctx.axis_names) - set(ctx.manual_axes)


def auto_devices() -> int:
    """Devices the compiler would partition a call over at this point of
    the trace: those of the context mesh's axes not taken manual (which
    :func:`per_device` takes manual around a kernel), or, under plain
    jit, of the mesh the sharding hints carry
    (:func:`repro.parallel.hints.active_mesh`). A kernel can be called
    only where this is 1."""
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        return math.prod(ctx.shape[a] for a in _auto_axes(ctx))
    mesh = hints.active_mesh()
    return 1 if mesh is None else mesh.size


def per_device(kernel):
    """Decorator: run ``kernel`` on each device's own copy of its arrays.

    A Mosaic kernel cannot be partitioned by the compiler, so a call
    inside a manual region that leaves mesh axes auto (the train step's DP
    region on a data x model mesh) opens a nested region taking those axes
    manual too. The codec's operands are replicated over them (the
    shard-local bucket stream), so every device computes what the
    unpartitioned call would. Elsewhere the kernel is called as is. Only
    the array arguments enter the region; the others (config, flags,
    ``None``) are passed through unchanged.
    """
    @functools.wraps(kernel)
    def run(*args, **kwargs):
        ctx = jax.sharding.get_abstract_mesh()
        auto = _auto_axes(ctx)
        if not ctx.manual_axes or not auto:
            return kernel(*args, **kwargs)
        leaves, tree = jax.tree.flatten((args, kwargs))
        is_array = [isinstance(x, jax.Array) for x in leaves]

        def local(*arrays):
            it = iter(arrays)
            a, kw = jax.tree.unflatten(
                tree, [next(it) if arr else x
                       for x, arr in zip(leaves, is_array)])
            return kernel(*a, **kw)

        arrays = [x for x, arr in zip(leaves, is_array) if arr]
        return shard_map(local, mesh=ctx, in_specs=P(), out_specs=P(),
                         axis_names=auto)(*arrays)
    return run
