"""Mamba2 (state-space duality) blocks — chunked SSD train/prefill path
and the O(1)-state decode path.

Follows the minimal SSD formulation of Dao & Gu 2024 (arXiv:2405.21060),
single B/C group shared across heads:

  h_t = exp(dt_t * A_h) * h_{t-1} + dt_t * B_t (x)  (outer product)
  y_t = C_t . h_t + D_h * x_t

Training scans over chunks of length ``Q``: within a chunk the recurrence
is expanded into a (Q, Q) decay-masked quadratic form (MXU-friendly);
across chunks only the (H, P, N) state is carried — sub-quadratic in
sequence length, which is why the ssm/hybrid archs run the 500k cells.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init, rmsnorm, init_rmsnorm
from repro.parallel.hints import constrain


def init_mamba(key, cfg: ModelConfig):
    s = cfg.ssm
    D = cfg.d_model
    di = s.d_inner(D)
    nh = s.n_heads(D)
    dt_ = cfg.activation_dtype
    ks = jax.random.split(key, 8)
    conv_ch = di + 2 * s.d_state
    return {
        "wx": dense_init(ks[0], (D, di), D, dt_),
        "wz": dense_init(ks[1], (D, di), D, dt_),
        "wB": dense_init(ks[2], (D, s.d_state), D, dt_),
        "wC": dense_init(ks[3], (D, s.d_state), D, dt_),
        "wdt": dense_init(ks[4], (D, nh), D, dt_),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D_skip": jnp.ones((nh,), jnp.float32),
        "conv_w": dense_init(ks[5], (s.d_conv, conv_ch), s.d_conv, jnp.float32),
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "norm": init_rmsnorm(di),
        "wo": dense_init(ks[6], (di, D), di, dt_),
    }


def _causal_conv(u: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 state: jnp.ndarray = None):
    """Depthwise causal conv, window d_conv. u: (B, S, C); w: (d_conv, C).

    With ``state`` (B, d_conv-1, C) the conv continues a stream (decode).
    Returns (y, new_state)."""
    dconv = w.shape[0]
    if state is None:
        state = jnp.zeros((u.shape[0], dconv - 1, u.shape[-1]), u.dtype)
    ext = jnp.concatenate([state, u], axis=1)              # (B, S+dc-1, C)
    y = sum(ext[:, i:i + u.shape[1]] * w[i] for i in range(dconv)) + b
    new_state = ext[:, -(dconv - 1):] if dconv > 1 else state
    return jax.nn.silu(y).astype(u.dtype), new_state


@jax.named_scope("ssd_scan")
def _ssd_chunk_scan(xdt, dA, Bm, Cm, chunk: int):
    """Chunked SSD. xdt: (B,S,H,P) = x*dt;  dA: (B,S,H) = dt*A (negative);
    Bm, Cm: (B,S,N). Returns y (B,S,H,P)."""
    Bt, S, H, Pd = xdt.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S

    def padn(a):
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))

    xdt, dA, Bm, Cm = padn(xdt), padn(dA), padn(Bm), padn(Cm)
    xdt = xdt.reshape(Bt, nc, Q, H, Pd).transpose(1, 0, 2, 3, 4)
    dA = dA.reshape(Bt, nc, Q, H).transpose(1, 0, 2, 3)
    Bm = Bm.reshape(Bt, nc, Q, N).transpose(1, 0, 2, 3)
    Cm = Cm.reshape(Bt, nc, Q, N).transpose(1, 0, 2, 3)

    def chunk_step(state, inp):              # noqa: C901 — hot loop
        x_c, dA_c, B_c, C_c = inp            # (B,Q,H,P),(B,Q,H),(B,Q,N)
        cs = jnp.cumsum(dA_c, axis=1)        # (B,Q,H) inclusive
        total = cs[:, -1]                    # (B,H)
        # intra-chunk: decay(i,j) = exp(cs_i - cs_j) for i >= j.
        # Mask the *exponent* (not the product): i < j gives positive
        # diffs that overflow exp and NaN the backward through where().
        diff = cs[:, :, None, :] - cs[:, None, :, :]               # (B,Q,Q,H)
        causal = jnp.tril(jnp.ones((Q, Q), bool))
        diff = jnp.where(causal[None, :, :, None], diff, -1e30)
        dec = constrain(jnp.exp(diff), ("dp", None, None, "tp"))
        scores = jnp.einsum("bin,bjn->bij", C_c, B_c,
                            preferred_element_type=jnp.float32)
        M = constrain(scores[..., None] * dec, ("dp", None, None, "tp"))
        y_diag = constrain(
            jnp.einsum("bijh,bjhp->bihp", M, x_c,
                       preferred_element_type=jnp.float32),
            ("dp", None, "tp", None))
        # contribution of the carried state
        y_off = jnp.einsum("bin,bhpn->bihp", C_c, state,
                           preferred_element_type=jnp.float32) \
            * jnp.exp(cs)[..., None]
        # state update: decay to end of chunk
        w_in = jnp.exp(total[:, None, :] - cs)                     # (B,Q,H)
        new_state = state * jnp.exp(total)[:, :, None, None] \
            + jnp.einsum("bjn,bjhp,bjh->bhpn", B_c, x_c, w_in,
                         preferred_element_type=jnp.float32)
        return new_state, (y_diag + y_off)

    state0 = jnp.zeros((Bt, H, Pd, N), jnp.float32)
    final_state, ys = jax.lax.scan(chunk_step, state0, (xdt, dA, Bm, Cm))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bt, nc * Q, H, Pd)
    return y[:, :S], final_state


def mamba_forward(x: jnp.ndarray, p, cfg: ModelConfig,
                  return_state: bool = False):
    """Train/prefill forward. x: (B, S, D) -> (B, S, D) [, decode state]."""
    s = cfg.ssm
    B, S, D = x.shape
    di = s.d_inner(D)
    nh = s.n_heads(D)
    xz = x @ p["wx"]                                  # (B,S,di)
    z = x @ p["wz"]
    Bm = x @ p["wB"]
    Cm = x @ p["wC"]
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    conv_in = jnp.concatenate([xz, Bm.astype(xz.dtype), Cm.astype(xz.dtype)], -1)
    conv_out, conv_state = _causal_conv(conv_in, p["conv_w"].astype(xz.dtype),
                                        p["conv_b"].astype(xz.dtype))
    xz, Bm, Cm = (conv_out[..., :di],
                  conv_out[..., di:di + s.d_state].astype(jnp.float32),
                  conv_out[..., di + s.d_state:].astype(jnp.float32))
    xh = xz.reshape(B, S, nh, s.head_dim).astype(jnp.float32)
    xh = constrain(xh, ("dp", None, "tp", None))
    A = -jnp.exp(p["A_log"])                          # (H,) negative
    y, ssm_state = _ssd_chunk_scan(xh * dt[..., None], dt * A, Bm, Cm, s.chunk)
    y = y + xh * p["D_skip"][None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    out = y @ p["wo"]
    if return_state:
        return out, {"ssm": ssm_state, "conv": conv_state.astype(jnp.float32)}
    return out


def init_mamba_state(batch: int, cfg: ModelConfig, dtype=jnp.float32):
    s = cfg.ssm
    nh = s.n_heads(cfg.d_model)
    return {
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.d_state), dtype),
        "conv": jnp.zeros((batch, s.d_conv - 1,
                           s.d_inner(cfg.d_model) + 2 * s.d_state), dtype),
    }


def mamba_decode(x: jnp.ndarray, p, cfg: ModelConfig, state):
    """Single-token decode. x: (B, 1, D). Returns (y, new_state)."""
    s = cfg.ssm
    B, _, D = x.shape
    di = s.d_inner(D)
    nh = s.n_heads(D)
    xz = x @ p["wx"]
    z = x @ p["wz"]
    Bm = x @ p["wB"]
    Cm = x @ p["wC"]
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])
    conv_in = jnp.concatenate([xz, Bm.astype(xz.dtype), Cm.astype(xz.dtype)], -1)
    conv_out, conv_state = _causal_conv(
        conv_in, p["conv_w"].astype(xz.dtype), p["conv_b"].astype(xz.dtype),
        state["conv"].astype(xz.dtype))
    xz = conv_out[..., :di]
    Bm = conv_out[..., di:di + s.d_state].astype(jnp.float32)[:, 0]
    Cm = conv_out[..., di + s.d_state:].astype(jnp.float32)[:, 0]
    xh = xz.reshape(B, nh, s.head_dim).astype(jnp.float32)
    dt0 = dt[:, 0]                                    # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt0 * A)                             # (B,H)
    h = state["ssm"] * dA[:, :, None, None] \
        + jnp.einsum("bn,bhp,bh->bhpn", Bm, xh, dt0)
    y = jnp.einsum("bn,bhpn->bhp", Cm, h) + xh * p["D_skip"][None, :, None]
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return y @ p["wo"], {"ssm": h.astype(state["ssm"].dtype),
                         "conv": conv_state.astype(state["conv"].dtype)}
