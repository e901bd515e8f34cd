"""Dense GQA decoder (granite-3.0 shapes): program fields, weights, counts,
reference.

``FIELDS`` maps the configuration file's keys (named as in the model's
published ``config.json``) to the program's ``ModelConfig`` fields;
``WIDTH_FIELDS`` are those fields that are widths. ``draw`` gives each
leaf of the program's parameter tree its initial values.

The reference is the decoder written out plainly in float32: pre-norm
blocks of RMSNorm, grouped-query attention with rotary embeddings in
the rotate-half convention, causal softmax scaled by 1/sqrt(head_dim),
and a SwiGLU MLP; a final RMSNorm and the output head. The loss is the
token-mean cross entropy plus ``1e-4 * mean(logsumexp^2)``, the z-loss
the program's objective adds. Attention runs in blocks of query rows,
each under ``jax.checkpoint``, so that a 4096-token sequence fits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FIELDS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}
WIDTH_FIELDS = ("d_model", "n_heads", "n_kv_heads", "d_ff")
Z_LOSS = 1e-4
Q_BLOCK = 512


def model_fields(m, base):
    """``ModelConfig`` fields that replace those of ``base``, the registry
    entry's ``ModelConfig``."""
    return {f: m[k] for k, f in FIELDS.items()}


def draw(key, name, shape):
    """Initial float32 values of the leaf ``name``: norm scales 1, biases
    0, every matrix N(0, 0.02)."""
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name in ("bq", "bk", "bv"):
        return jnp.zeros(shape, jnp.float32)
    return 0.02 * jax.random.normal(key, shape, jnp.float32)


def head_dim(m) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def matmul_params(m) -> int:
    """Weights that enter a matrix product once per token (the embedding
    lookup is a gather and does not count; the output head does)."""
    D, F, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    return m["num_hidden_layers"] * (attn + 3 * D * F) + D * V


def flops_per_token(m, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward.

    6 x matmul parameters, plus attention's two products (QK^T and PV)
    with the causal convention: a token attends to (S + 1) / 2 positions
    on average, so each layer adds 3 x 2 x 2 x H x hd x (S + 1) / 2.
    Recomputation does not count."""
    H, hd = m["num_attention_heads"], head_dim(m)
    attn = m["num_hidden_layers"] * 6 * H * hd * (seq_len + 1)
    return 6.0 * matmul_params(m) + attn


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd): rotate-half rotary embedding at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, dot):
    """Causal softmax attention, q (S, H, hd), k/v (S, H, hd)."""
    S, _, hd = q.shape
    blk = min(Q_BLOCK, S)
    outs = []
    for a in range(0, S, blk):
        b = min(a + blk, S)

        @jax.checkpoint
        def one(qb, kb, vb, a=a, b=b):
            s = dot("qhd,khd->hqk", qb, kb) / math.sqrt(hd)
            mask = (a + jnp.arange(b - a))[:, None] >= jnp.arange(b)[None, :]
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return dot("hqk,khd->qhd", p, vb)

        outs.append(one(q[a:b], k[:b], v[:b]))
    return jnp.concatenate(outs, 0)


def _layer(x, p, m, dot):
    S = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], head_dim(m)
    eps = m["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    q = dot("sd,df->sf", h, p["attn"]["wq"]).reshape(S, H, hd)
    k = dot("sd,df->sf", h, p["attn"]["wk"]).reshape(S, KV, hd)
    v = dot("sd,df->sf", h, p["attn"]["wv"]).reshape(S, KV, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=1)      # head h reads kv head h // rep
    v = jnp.repeat(v, H // KV, axis=1)
    o = _attention(q, k, v, dot).reshape(S, H * hd)
    x = x + dot("sf,fd->sd", o, p["attn"]["wo"])
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(dot("sd,df->sf", h, f["w_gate"])) * dot("sd,df->sf", h, f["w_up"])
    return x + dot("sf,fd->sd", g, f["w_down"])


def sequence_loss(params, tokens, labels, m, dot):
    """Loss of one sequence. ``params``: the program's parameter tree in
    float32; tokens, labels: (S,) int32."""
    V = m["vocab_size"]
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        return _layer(x, p, m, dot), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"], m["rms_norm_eps"])
    if m["tie_word_embeddings"]:
        logits = dot("sd,vd->sv", x, params["embed"][:V])
    else:
        logits = dot("sd,dv->sv", x, params["lm_head"][:, :V])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - ll) + Z_LOSS * jnp.mean(lse * lse)
