"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layer parameters are stacked on a leading layer axis (``vmap`` at init)
and consumed with ``lax.scan`` so the lowered HLO contains one layer body
regardless of depth — essential to keep 512-device AOT compiles fast.

Public entry points (all pure):
  init_lm(key, cfg)                              -> params
  lm_loss(params, cfg, batch, rng)               -> (loss, metrics)
  lm_prefill(params, cfg, tokens, ...)           -> (logits_last, cache)
  lm_decode(params, cfg, token, cache, position) -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import layers as L
from . import ssm as S
from repro.parallel.hints import constrain


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str) -> Dict[str, Any]:
    """One layer's params. kind: "attn" | "ssm"; FFN chosen by cfg/moe."""
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": L.init_rmsnorm(cfg.d_model)}
    if kind == "ssm":
        p["mamba"] = S.init_mamba(ks[0], cfg)
        return p
    p["attn"] = L.init_attention(ks[0], cfg)
    p["ln2"] = L.init_rmsnorm(cfg.d_model)
    if cfg.moe is not None:
        p["moe"] = L.init_moe(ks[1], cfg)
    else:
        p["ffn"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff,
                              cfg.activation_dtype)
    return p


def _init_hybrid_superblock(key, cfg: ModelConfig) -> Dict[str, Any]:
    """One jamba-style super-block of ``attn_period`` layers."""
    ks = jax.random.split(key, cfg.attn_period * 3)
    p: Dict[str, Any] = {}
    for pos in range(cfg.attn_period):
        kind = "attn" if pos == cfg.attn_offset else "ssm"
        sub: Dict[str, Any] = {"ln1": L.init_rmsnorm(cfg.d_model)}
        if kind == "attn":
            sub["attn"] = L.init_attention(ks[3 * pos], cfg)
        else:
            sub["mamba"] = S.init_mamba(ks[3 * pos], cfg)
        # FFN on every layer: MoE every ``every_k_layers`` positions
        # (jamba's k=2 puts MoE on odd positions, dense on even).
        sub["ln2"] = L.init_rmsnorm(cfg.d_model)
        k_moe = cfg.moe.every_k_layers if cfg.moe is not None else 0
        if cfg.moe is not None and pos % k_moe == k_moe - 1:
            sub["moe"] = L.init_moe(ks[3 * pos + 1], cfg)
        else:
            sub["ffn"] = L.init_mlp(ks[3 * pos + 1], cfg.d_model, cfg.d_ff,
                                    cfg.activation_dtype)
        p[f"pos{pos}"] = sub
    return p


def init_lm(key, cfg: ModelConfig):
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    params: Dict[str, Any] = {
        "embed": L.dense_init(k_emb, (cfg.padded_vocab, cfg.d_model),
                              cfg.d_model, dt),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            k_head, (cfg.d_model, cfg.padded_vocab), cfg.d_model, dt)
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_period
        keys = jax.random.split(k_layers, n_super)
        params["superblocks"] = jax.vmap(
            lambda k: _init_hybrid_superblock(k, cfg))(keys)
    else:
        kind = "ssm" if cfg.family == "ssm" else "attn"
        keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_block(k, cfg, kind))(keys)
    return params


# ----------------------------------------------------------------------
# Blocks (forward)
# ----------------------------------------------------------------------

def _apply_ffn(x, p, cfg: ModelConfig, decode: bool = False,
               ep_exchange=None):
    """Post-attention FFN (dense or MoE). x: (B, S, D) -> (out, aux).

    ``ep_exchange`` (PR 8): the expert-parallel all-to-all combine wire,
    threaded from the train step (see :func:`repro.models.layers.moe_ffn`);
    train-path only, decode keeps the local combine.
    """
    B, Sq, D = x.shape
    if "moe" in p:
        cf = cfg.moe.capacity_factor_decode if decode else None
        out, aux = L.moe_ffn(x.reshape(B * Sq, D), p["moe"], cfg.moe,
                             capacity_factor=cf,
                             ep_exchange=None if decode else ep_exchange)
        return out.reshape(B, Sq, D), aux
    with jax.named_scope("mlp"):
        return L.mlp(x, p["ffn"]), jnp.float32(0.0)


def _attn_block(x, p, cfg: ModelConfig, positions, ep_exchange=None):
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("attention"):
        o, kv = L.attention_train(h, p["attn"], cfg, positions=positions)
    x = x + o
    h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
    ff, aux = _apply_ffn(h, p, cfg, ep_exchange=ep_exchange)
    return x + ff, aux, kv


def _ssm_block(x, p, cfg: ModelConfig, ep_exchange=None):
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    with jax.named_scope("mamba"):
        x = x + S.mamba_forward(h, p["mamba"], cfg)
    if "ln2" in p:
        h = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        ff, aux = _apply_ffn(h, p, cfg, ep_exchange=ep_exchange)
        return x + ff, aux
    return x, jnp.float32(0.0)


# ----------------------------------------------------------------------
# Train forward
# ----------------------------------------------------------------------

def _embed(params, cfg: ModelConfig, tokens, vis_embed=None):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if vis_embed is not None:
            x = jnp.concatenate([vis_embed.astype(x.dtype), x], axis=1)
        return constrain(x, ("dp", None, None))


def _unembed(params, cfg: ModelConfig, x):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head).astype(jnp.float32)
    logits = L.mask_padded_vocab(logits, cfg)
    return constrain(logits, ("dp", None, "tp"))


def lm_hidden(params, cfg: ModelConfig, tokens, vis_embed=None,
              remat: str = "none", ep_exchange=None):
    """Token (+ visual prefix) embedding through all blocks. -> (x, aux)."""
    x = _embed(params, cfg, tokens, vis_embed)
    Sq = x.shape[1]

    # positions is built *inside* each scan body, so the iota is a plain
    # op there rather than a scan-level constant operand.
    def _positions():
        return jnp.arange(Sq)[None, :]

    if cfg.family == "hybrid":
        def super_body(carry, p_sb):
            xx, aux = carry
            for pos in range(cfg.attn_period):
                sub = p_sb[f"pos{pos}"]
                if pos == cfg.attn_offset:
                    xx, a, _ = _attn_block(xx, sub, cfg, _positions(),
                                           ep_exchange=ep_exchange)
                else:
                    xx, a = _ssm_block(xx, sub, cfg,
                                       ep_exchange=ep_exchange)
                aux = aux + a
            return (xx, aux), None
        body = super_body
        stacked = params["superblocks"]
    elif cfg.family == "ssm":
        def body(carry, p_l):
            xx, aux = carry
            xx, a = _ssm_block(xx, p_l, cfg, ep_exchange=ep_exchange)
            return (xx, aux + a), None
        stacked = params["layers"]
    else:
        def body(carry, p_l):
            xx, aux = carry
            xx, a, _ = _attn_block(xx, p_l, cfg, _positions(),
                                   ep_exchange=ep_exchange)
            return (xx, aux + a), None
        stacked = params["layers"]

    if remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "block_nocse":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stacked)
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def lm_loss(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            remat: str = "none", ep_exchange=None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Causal-LM cross entropy. batch: tokens (B,S), labels (B,S),
    optional vis_embed (B,V,D). Loss only over token positions.
    ``ep_exchange``: the PR 8 expert-parallel combine wire (see
    :func:`lm_hidden` / :func:`repro.models.layers.moe_ffn`)."""
    tokens, labels = batch["tokens"], batch["labels"]
    vis = batch.get("vis_embed")
    x, aux = lm_hidden(params, cfg, tokens, vis, remat=remat,
                       ep_exchange=ep_exchange)
    if vis is not None:
        x = x[:, vis.shape[1]:]                     # text positions only
    with jax.named_scope("head_loss"):
        logits = _unembed(params, cfg, x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = jnp.mean(lse - ll)
        zloss = 1e-4 * jnp.mean(jnp.square(lse))
        loss = nll + zloss + 0.01 * aux
    return loss, {"nll": nll, "aux": aux, "zloss": zloss}


# ----------------------------------------------------------------------
# Serving: prefill + decode with caches
# ----------------------------------------------------------------------

def init_cache(params, cfg: ModelConfig, batch: int, max_len: int,
               dtype=None):
    """Allocate the per-layer decode cache pytree."""
    dt = dtype or cfg.activation_dtype
    KV, hd = cfg.n_kv_heads, cfg.hd
    if cfg.family == "ssm":
        st = S.init_mamba_state(batch, cfg)
        return {"ssm": jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), st)}
    if cfg.family == "hybrid":
        n_super = cfg.n_layers // cfg.attn_period
        st = S.init_mamba_state(batch, cfg)
        mamba = jax.tree.map(
            lambda a: jnp.zeros((n_super, cfg.attn_period - 1) + a.shape,
                                a.dtype), st)
        kv = {"k": jnp.zeros((n_super, batch, max_len, KV, hd), dt),
              "v": jnp.zeros((n_super, batch, max_len, KV, hd), dt)}
        return {"mamba": mamba, "kv": kv}
    # dense / moe / vlm
    return {"k": jnp.zeros((cfg.n_layers, batch, max_len, KV, hd), dt),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, KV, hd), dt)}


def lm_decode(params, cfg: ModelConfig, token: jnp.ndarray, cache,
              position) -> Tuple[jnp.ndarray, Any]:
    """One decode step. token: (B,) int32; position: scalar int32 (tokens
    0..position-1 are already in the cache). Returns (logits (B,V), cache)."""
    x = _embed(params, cfg, token[:, None])

    if cfg.family == "ssm":
        def body(xx, inp):
            p_l, st = inp
            h = L.rmsnorm(xx, p_l["ln1"], cfg.norm_eps)
            o, st2 = S.mamba_decode(h, p_l["mamba"], cfg, st)
            return xx + o, st2
        x, new_st = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        new_cache = {"ssm": new_st}
    elif cfg.family == "hybrid":
        def body(xx, inp):
            p_sb, mamba_st, kv = inp
            new_states = []
            si = 0
            k_c, v_c = kv["k"], kv["v"]
            for pos in range(cfg.attn_period):
                sub = p_sb[f"pos{pos}"]
                h = L.rmsnorm(xx, sub["ln1"], cfg.norm_eps)
                if pos == cfg.attn_offset:
                    o, k_c, v_c = L.attention_decode(
                        h, sub["attn"], cfg, k_c, v_c, position)
                else:
                    st = jax.tree.map(lambda a: a[si], mamba_st)
                    o, st2 = S.mamba_decode(h, sub["mamba"], cfg, st)
                    new_states.append(st2)
                    si += 1
                xx = xx + o
                h = L.rmsnorm(xx, sub["ln2"], cfg.norm_eps)
                ff, _ = _apply_ffn(h, sub, cfg, decode=True)
                xx = xx + ff
            stacked_st = jax.tree.map(lambda *a: jnp.stack(a), *new_states)
            return xx, (stacked_st, {"k": k_c, "v": v_c})
        x, (new_mamba, new_kv) = jax.lax.scan(
            body, x, (params["superblocks"], cache["mamba"], cache["kv"]))
        new_cache = {"mamba": new_mamba, "kv": new_kv}
    else:
        def body(xx, inp):
            p_l, k_c, v_c = inp
            h = L.rmsnorm(xx, p_l["ln1"], cfg.norm_eps)
            o, k_c, v_c = L.attention_decode(h, p_l["attn"], cfg, k_c, v_c,
                                             position)
            xx = xx + o
            h = L.rmsnorm(xx, p_l["ln2"], cfg.norm_eps)
            ff, _ = _apply_ffn(h, p_l, cfg, decode=True)
            return xx + ff, (k_c, v_c)
        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": nk, "v": nv}

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)[:, 0]
    return logits, new_cache


def lm_prefill(params, cfg: ModelConfig, tokens, max_len: Optional[int] = None,
               vis_embed=None):
    """Prefill: run the full prompt, return (last logits, populated cache).

    For attention families the per-layer K/V are collected from the train
    forward; SSM caches replay the chunked scan's final state.
    """
    B, Sq = tokens.shape
    max_len = max_len or Sq
    x = _embed(params, cfg, tokens, vis_embed)
    Sfull = x.shape[1]
    positions = jnp.arange(Sfull)[None, :]

    if cfg.family in ("dense", "moe", "vlm", "encdec"):
        def body(xx, p_l):
            h = L.rmsnorm(xx, p_l["ln1"], cfg.norm_eps)
            o, (k, v) = L.attention_train(h, p_l["attn"], cfg, positions)
            xx = xx + o
            h = L.rmsnorm(xx, p_l["ln2"], cfg.norm_eps)
            ff, _ = _apply_ffn(h, p_l, cfg)
            return xx + ff, (k, v)
        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        pad = max(0, max_len - Sfull)   # vlm prefix may exceed max_len
        cache = {"k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                 "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))}
    elif cfg.family == "ssm":
        def body(xx, p_l):
            h = L.rmsnorm(xx, p_l["ln1"], cfg.norm_eps)
            o, st = S.mamba_forward(h, p_l["mamba"], cfg, return_state=True)
            return xx + o, st
        x, states = jax.lax.scan(body, x, params["layers"])
        cache = {"ssm": states}
    elif cfg.family == "hybrid":
        def body(xx, p_sb):
            sts, kv = [], None
            for pos in range(cfg.attn_period):
                sub = p_sb[f"pos{pos}"]
                h = L.rmsnorm(xx, sub["ln1"], cfg.norm_eps)
                if pos == cfg.attn_offset:
                    o, kv = L.attention_train(h, sub["attn"], cfg, positions)
                else:
                    o, st = S.mamba_forward(h, sub["mamba"], cfg,
                                            return_state=True)
                    sts.append(st)
                xx = xx + o
                h = L.rmsnorm(xx, sub["ln2"], cfg.norm_eps)
                ff, _ = _apply_ffn(h, sub, cfg)
                xx = xx + ff
            stacked = jax.tree.map(lambda *a: jnp.stack(a), *sts)
            return xx, (stacked, kv)
        x, (mamba_st, (ks, vs)) = jax.lax.scan(body, x, params["superblocks"])
        pad = max(0, max_len - Sfull)
        cache = {"mamba": mamba_st,
                 "kv": {"k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
                        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))}}
    else:
        raise NotImplementedError(cfg.family)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x[:, -1:])[:, 0]
    return logits, cache
