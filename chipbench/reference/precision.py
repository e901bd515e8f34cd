"""How the reference multiplies: float32 at full precision, or the control.

The reference computes every matrix product in float32 with
``Precision.HIGHEST`` (a TPU runs float32 products in bfloat16 passes
unless told otherwise). The control is the same reference one precision
step below what the configurations state (bfloat16): float8 e4m3 for
every operand of every product, in the forward pass and in the backward
pass (the incoming gradient included), each tensor with its own scale
(its largest magnitude onto e4m3's largest, 448), accumulated in
float32. That is the fp8 training step a later change might be tempted
to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fp8_round(x):
    """x rounded to e4m3 under a per-tensor scale, returned in float32."""
    scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return _einsum(spec, fp8_round(a), fp8_round(b))


def _fp8_fwd(spec, a, b):
    qa, qb = fp8_round(a), fp8_round(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(fp8_round(g))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


class Dot:
    """``einsum`` for the reference: ``Dot("f32")`` or ``Dot("fp8")``."""

    def __init__(self, mode: str = "f32"):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {mode!r}")
        self.mode = mode

    def __call__(self, spec: str, a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        if self.mode == "fp8":
            return _fp8_einsum(spec, a, b)
        return _einsum(spec, a, b)
