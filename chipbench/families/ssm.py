"""Mamba-2 (SSD) language model: program fields, weights, counts,
reference.

``FIELDS`` maps the configuration file's keys (the published
``MambaLMHeadModel`` / ``Mamba2`` argument names) to the program's
``ModelConfig`` fields; the SSD sizes go to its ``ssm`` group.
``WIDTH_FIELDS`` are the fields that are widths. ``draw`` gives each
leaf of the program's parameter tree its initial values, as Mamba-2
initialises them.

The reference is a Mamba-2 block written out plainly in float32:
separate input projections x, z, B, C and dt, a depthwise causal
convolution over (x, B, C) with SiLU, ``dt = softplus(x W_dt + b)``,
``A = -exp(A_log)``, the selective state space

    y_i = sum_{j <= i} (C_i . B_j) exp(sum_{t=j+1..i} dt_t A) dt_j x_j + D x_i,

then the gated RMSNorm ``norm(y * silu(z))`` and the output projection.
The state space runs in its quadratic (attention-like) form, in blocks
of query rows, not by the program's chunked scan: a different algorithm
for the same sum. Decay exponents are summed from the block's own start
so that no difference of two long cumulative sums is taken.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

FIELDS = {
    "d_model": "d_model",
    "n_layer": "n_layers",
    "vocab_size": "vocab",
    "norm_epsilon": "norm_eps",
    "tie_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
SSM_FIELDS = {
    "d_state": "d_state",
    "expand": "expand",
    "headdim": "head_dim",
    "d_conv": "d_conv",
    "chunk_size": "chunk",
}
WIDTH_FIELDS = ("d_model", "n_heads", "ssm")
Z_LOSS = 1e-4
Q_BLOCK = 512


def _sizes(m):
    di = m["expand"] * m["d_model"]
    return di, di // m["headdim"], m["headdim"], m["d_state"]


def model_fields(m, base):
    """``ModelConfig`` fields that replace those of ``base``, the registry
    entry's ``ModelConfig``: ``FIELDS``, the head count the program
    derives, and the ``ssm`` group."""
    _, nh, _, _ = _sizes(m)
    fields = {f: m[k] for k, f in FIELDS.items()}
    fields.update(n_heads=nh, n_kv_heads=nh, ssm=dataclasses.replace(
        base.ssm, **{f: m[k] for k, f in SSM_FIELDS.items()}))
    return fields


def draw(key, name, shape):
    """Initial float32 values of the leaf ``name``: norm scales and the
    skip D 1; A = -exp(A_log) with A uniform in [1, 16]; dt_bias the
    inverse softplus of dt, log-uniform in [1e-3, 1e-1]; the convolution
    uniform in +-1/sqrt(width), its bias 0; every matrix N(0, 0.02)."""
    f32 = jnp.float32
    if name in ("scale", "D_skip"):
        return jnp.ones(shape, f32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "conv_b":
        return jnp.zeros(shape, f32)
    if name == "conv_w":
        s = 1.0 / math.sqrt(shape[-2])
        return jax.random.uniform(key, shape, f32, -s, s)
    return 0.02 * jax.random.normal(key, shape, f32)


def matmul_params(m) -> int:
    D, V = m["d_model"], m["vocab_size"]
    di, nh, _, N = _sizes(m)
    per_layer = 2 * D * di + 2 * D * N + D * nh + di * D
    return m["n_layer"] * per_layer + D * V


def flops_per_token(m, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward (x3).

    6 x matmul parameters, plus per layer the state space in its chunked
    form with chunk Q (``chunk_size``) and the causal convention (a token
    meets (Q + 1) / 2 positions of its chunk): scores C.B (2 N (Q+1)/2),
    their product with x (2 H P (Q+1)/2), the carried state read
    (2 H P N) and written (2 H P N), and the convolution
    (2 d_conv (d_inner + 2 N)). Recomputation does not count."""
    di, nh, P, N = _sizes(m)
    Q = min(m["chunk_size"], seq_len)
    half = (Q + 1) / 2
    ssd = 2 * N * half + 2 * nh * P * half + 4 * nh * P * N
    conv = 2 * m["d_conv"] * (di + 2 * N)
    return 6.0 * matmul_params(m) + 3.0 * m["n_layer"] * (ssd + conv)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _ssd(xdt, dA, B, C, dot):
    """xdt (S, H, P), dA (S, H), B/C (S, N) -> y (S, H, P)."""
    S = xdt.shape[0]
    blk = min(Q_BLOCK, S)
    outs = []
    for a in range(0, S, blk):
        b = min(a + blk, S)

        @jax.checkpoint
        def one(xdt_k, dA_k, B_k, C_q, a=a, b=b):
            fwd = jnp.cumsum(dA_k[a:b], axis=0)                # sum_{a..i}
            if a:
                # rc_j = sum_{t=j+1..a-1} dA_t for the keys before the block
                rc = jnp.cumsum(dA_k[1:a][::-1], axis=0)[::-1]
                rc = jnp.concatenate([rc, jnp.zeros_like(dA_k[:1])], 0)
                key = jnp.concatenate([rc, -fwd], 0)
            else:
                key = -fwd
            expo = fwd[:, None, :] + key[None, :, :]           # (q, b, H)
            mask = (a + jnp.arange(b - a))[:, None] >= jnp.arange(b)[None, :]
            dec = jnp.exp(jnp.where(mask[:, :, None], expo, -jnp.inf))
            scores = dot("in,jn->ij", C_q, B_k)
            return dot("ijh,jhp->ihp", scores[:, :, None] * dec, xdt_k)

        outs.append(one(xdt[:b], dA[:b], B[:b], C[a:b]))
    return jnp.concatenate(outs, 0)


def _mixer(x, p, m, dot):
    S = x.shape[0]
    di, nh, P, N = _sizes(m)
    eps = m["norm_epsilon"]
    xz = dot("sd,df->sf", x, p["wx"])
    z = dot("sd,df->sf", x, p["wz"])
    Bm = dot("sd,dn->sn", x, p["wB"])
    Cm = dot("sd,dn->sn", x, p["wC"])
    dt = jax.nn.softplus(dot("sd,dh->sh", x, p["wdt"]) + p["dt_bias"])
    u = jnp.concatenate([xz, Bm, Cm], -1)
    K = p["conv_w"].shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u], 0)
    u = jax.nn.silu(sum(ext[i:i + S] * p["conv_w"][i] for i in range(K))
                    + p["conv_b"])
    xh = u[:, :di].reshape(S, nh, P)
    Bm, Cm = u[:, di:di + N], u[:, di + N:]
    A = -jnp.exp(p["A_log"])
    y = _ssd(xh * dt[:, :, None], dt * A, Bm, Cm, dot)
    y = (y + xh * p["D_skip"][None, :, None]).reshape(S, di)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm"]["scale"], eps)
    return dot("sf,fd->sd", y, p["wo"])


def sequence_loss(params, tokens, labels, m, dot):
    """Loss of one sequence. ``params``: the program's parameter tree in
    float32; tokens, labels: (S,) int32."""
    V, eps = m["vocab_size"], m["norm_epsilon"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        return x + _mixer(h, p["mamba"], m, dot), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    if m["tie_embeddings"]:
        logits = dot("sd,vd->sv", x, params["embed"][:V])
    else:
        logits = dot("sd,dv->sv", x, params["lm_head"][:, :V])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll) + Z_LOSS * jnp.mean(lse * lse)
