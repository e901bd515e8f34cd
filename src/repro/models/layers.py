"""Shared neural-net layers: norms, RoPE, flash attention, SwiGLU, MoE.

Functional style: ``init_*`` builds param subtrees (plain dicts of
jnp arrays), ``apply`` functions are pure. Layer params are stacked on a
leading layer axis by the model builders and consumed via ``lax.scan``.

Sharding is communicated through *logical* activation hints
(:mod:`repro.parallel.hints`) so the layers never hard-code mesh axes and
run unchanged on a single CPU device.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from .config import ModelConfig, MoEConfig
from repro import compat
from repro.parallel.hints import constrain


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(x: jnp.ndarray, p, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(x: jnp.ndarray, p, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def mask_padded_vocab(logits: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """-inf the padding columns of a padded-vocab logit tensor."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(col < cfg.vocab, logits, -1e30)


# ----------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------

def rope_frequencies(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                      # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (...,S,1,hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# Initializers
# ----------------------------------------------------------------------

def dense_init(key, shape, in_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.uniform(key, shape, jnp.float32, -scale, scale)
            .astype(dtype))


# ----------------------------------------------------------------------
# Attention (GQA, optional QKV bias, flash-style blockwise softmax)
# ----------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    D, hd = cfg.d_model, cfg.hd
    H, KV = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), D, dt),
        "wk": dense_init(ks[1], (D, KV * hd), D, dt),
        "wv": dense_init(ks[2], (D, KV * hd), D, dt),
        "wo": dense_init(ks[3], (H * hd, D), H * hd, dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    return p


def _project_qkv(x, p, cfg: ModelConfig, kv_input=None):
    """Returns q (B,S,H,hd) merged-head, k/v (B,Skv,KV,hd).

    q is constrained to head sharding here, while still bf16 — §Perf
    iteration 3: letting GSPMD reshard at RoPE's internal f32 reshape
    doubled the per-layer gather bytes."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    kv_x = x if kv_input is None else kv_input
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(B, S, H, hd), (None, None, "tp", None))
    k = k.reshape(B, kv_x.shape[1], KV, hd)
    v = v.reshape(B, kv_x.shape[1], KV, hd)
    return q, k, v


# The fused TPU kernel: JAX's splash attention, multi-head, with its fused
# backward. It takes no score scale, so q is scaled before the call, which
# is exact only where 1/sqrt(hd) is a power of two: of the registry's head
# sizes, 64 (hd 128 would round q twice). Its tiles are the largest power
# of two up to the cap that divides the length, at least the kernel's
# 128-lane minimum; the caps were chosen on a TPU v5e at S 4096, hd 64
# (``benchmarks/attention_sweep.py``).
_KERNEL_HEAD_DIM = 64
_KERNEL_MIN_BLOCK = 128
_KERNEL_BLOCK = 1024
_KERNEL_KV_COMPUTE = 512


def attention_kernel_blocks(sq: int, skv: int, hd: int, q_offset: int,
                            backend: str, auto_devices: int
                            ) -> Optional[splash.BlockSizes]:
    """The fused TPU kernel's block sizes where it computes this call,
    else None (the call takes :func:`blockwise_attention`).

    The kernel engages only where it computes what the blockwise path
    computes and can be compiled: on a TPU; self-attention from position
    0 (``q_offset == 0``, ``sq == skv``), so its causal mask ``col <= row``
    is the program's; a length that divides by its blocks; the head size
    whose scale is exact (``_KERNEL_HEAD_DIM``); and a call the compiler
    need not partition (``auto_devices`` 1), since Mosaic kernels cannot
    be partitioned: heads sharded over a ``tp`` axis of more than one
    device keep the blockwise path.
    """
    if (backend != "tpu" or q_offset != 0 or sq != skv or auto_devices != 1
            or hd != _KERNEL_HEAD_DIM):
        return None
    b = _KERNEL_BLOCK
    while sq % b:
        b //= 2
    if b < _KERNEL_MIN_BLOCK:
        return None
    compute = min(b, _KERNEL_KV_COMPUTE)
    return splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=compute,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool, q_block: int, kv_block: int = 1024,
                    q_offset: int = 0) -> jnp.ndarray:
    """Attention for training and prefill: the fused TPU kernel
    (:func:`kernel_attention`) where :func:`attention_kernel_blocks` admits
    the call, else :func:`blockwise_attention`. Arguments as the
    latter's."""
    blocks = attention_kernel_blocks(q.shape[1], k.shape[1], q.shape[-1],
                                     q_offset, jax.default_backend(),
                                     compat.auto_devices())
    if blocks is None:
        return blockwise_attention(q, k, v, causal, q_block, kv_block,
                                   q_offset)
    return kernel_attention(q, k, v, causal, blocks)


def kernel_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     causal: bool, blocks) -> jnp.ndarray:
    """The TPU Pallas splash attention kernel that ships with JAX
    (``jax.experimental.pallas.ops.tpu.splash_attention``): one fused
    forward and one fused backward, skipping the blocks the causal mask
    hides. Scores in float32 from q and k, as in the blockwise path; the
    PV products run in float32.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd). KV heads are expanded to the
    full head count, as the blockwise path does, and the heads moved
    before the sequence around the call. q enters scaled by 1/sqrt(hd),
    exact for the head sizes :func:`attention_kernel_blocks` admits. Where
    mesh axes are still auto (all of one device) the call runs per device
    (:func:`repro.compat.per_device`).
    """
    sq, h, hd = q.shape[1:]
    rep = h // k.shape[2]
    mask = (splash.CausalMask if causal else splash.FullMask)((sq, k.shape[1]))
    kernel = splash.make_splash_mha(
        splash.MultiHeadMask([mask] * h), block_sizes=blocks,
        head_shards=1, q_seq_shards=1)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    o = compat.per_device(jax.vmap(kernel))(
        heads_first(q * (1.0 / math.sqrt(hd))),
        heads_first(jnp.repeat(k, rep, axis=2)),
        heads_first(jnp.repeat(v, rep, axis=2)))
    return heads_first(o)


@jax.named_scope("blockwise_attention")
def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool, q_block: int, kv_block: int = 1024,
                        q_offset: int = 0) -> jnp.ndarray:
    """Blockwise online-softmax attention (the lax analogue of flash).

    q: (B, Sq, H, hd);  k, v: (B, Skv, KV, hd).
    Memory peak is O(bq * bk) per (batch, head) rather than O(Sq * Skv).
    ``q_offset`` positions q tokens at ``q_offset + i`` for causal masking
    (used by decode/prefill-with-cache paths).

    Perf note (§Perf iteration 1): KV heads are *expanded* to the full
    head count before the score einsums, so head_dim is the only
    contraction. With grouped (KV, rep) operands GSPMD sharded head_dim
    across model shards (4 KV heads cannot cover 16-way TP) and inserted
    a partial-sum all-reduce of the scores inside both flash loops —
    ~1.5 TB/device/step on qwen2-7b. Merged heads shard (unevenly) on the
    head axis instead: zero collectives inside the loops, one K/V head
    broadcast per layer.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    bq = min(q_block, Sq)
    bk = min(kv_block, Skv)
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    qh = q
    kh = jnp.broadcast_to(k[:, :, :, None, :],
                          (B, Skv, KV, rep, hd)).reshape(B, Skv, H, hd)
    vh = jnp.broadcast_to(v[:, :, :, None, :],
                          (B, Skv, KV, rep, hd)).reshape(B, Skv, H, hd)
    kh = constrain(kh, (None, None, "tp", None))
    vh = constrain(vh, (None, None, "tp", None))
    # pad to block multiples
    qp = jnp.pad(qh, ((0, 0), (0, nq * bq - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(kh, ((0, 0), (0, nk * bk - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(vh, ((0, 0), (0, nk * bk - Skv), (0, 0), (0, 0)))
    qs = qp.reshape(B, nq, bq, H, hd).transpose(1, 0, 2, 3, 4)
    ks = kp.reshape(B, nk, bk, H, hd).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, bk, H, hd).transpose(1, 0, 2, 3, 4)

    # Block indices ride in the scan *carries* and positions are built by
    # in-body iotas, so no constant (arange) rides among the scan xs.
    @jax.checkpoint  # flash backward: recompute probs per q-block
    def q_step(qi, q_blk):  # instead of saving the O(Sq*Skv) attn matrix
        q_pos = q_offset + qi * bq + jnp.arange(bq)     # (bq,)

        def kv_step(carry, kv_blk):
            m, l, acc, ki = carry
            k_blk, v_blk = kv_blk
            kpos = ki * bk + jnp.arange(bk)             # (bk,)
            kval = kpos < Skv
            # (§Perf iteration 2 tried bf16 score emission here — wire
            # bytes were unchanged, the f32 resharding happens at the
            # layer level, not in this einsum's cotangents. Reverted.)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.broadcast_to(kval[None, :], (bq, bk))
            if causal:
                mask = mask & (q_pos[:, None] >= kpos[None, :])
            s = jnp.where(mask[None, None, :, :], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
            return (m_new, l_new, acc_new, ki + 1), None

        m0 = jnp.full((B, H, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, bq), jnp.float32)
        a0 = jnp.zeros((B, bq, H, hd), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            kv_step, (m0, l0, a0, jnp.int32(0)), (ks, vs))
        l = jnp.maximum(l, 1e-30)
        out = acc / l.transpose(0, 2, 1)[..., None]
        return qi + 1, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, jnp.int32(0), qs)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * bq, H, hd)
    return out[:, :Sq]


def attention_train(x, p, cfg: ModelConfig, positions=None, causal=True,
                    kv_input=None):
    """Full self(/cross)-attention for training/prefill. x: (B,S,D)."""
    B, S, D = x.shape
    q, k, v = _project_qkv(x, p, cfg, kv_input=kv_input)   # q (B,S,H,hd)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if kv_input is None:   # self-attention gets RoPE
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions[:, : k.shape[1]], cfg.rope_theta)
    o = flash_attention(q, k, v, causal=causal, q_block=cfg.q_block)
    o = o.reshape(B, S, -1)
    return o @ p["wo"], (k, v)


def attention_decode(x, p, cfg: ModelConfig, cache_k, cache_v, position,
                     rope: bool = True):
    """Single-token decode. x: (B,1,D); cache: (B,Skv,KV,hd).

    Softmax reduces over the (possibly sequence-sharded) cache axis; under
    GSPMD this lowers to the flash-decoding partial-max/-sum combine.
    """
    B = x.shape[0]
    KV, hd = cfg.n_kv_heads, cfg.hd
    q, k_new, v_new = _project_qkv(x, p, cfg)              # q (B,1,H,hd)
    if rope:
        pos = jnp.full((B, 1), position)
        q = apply_rope(q, pos, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    q = q.reshape(B, 1, KV, cfg.n_heads // KV, hd)
    # in-place cache update at `position`
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), position, 1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), position, 1)
    s = jnp.einsum("bqgrd,bkgd->bgrk", q, cache_k,
                   preferred_element_type=jnp.float32)  # Sq=1 contracts away
    s = s * (1.0 / math.sqrt(hd))
    valid = jnp.arange(cache_k.shape[1]) <= position
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", w.astype(cache_v.dtype), cache_v,
                   preferred_element_type=jnp.float32)
    o = o.astype(x.dtype).reshape(B, 1, -1)
    return o @ p["wo"], cache_k, cache_v


def attention_cross_decode(x, p, cfg: ModelConfig, enc_k, enc_v):
    """Cross-attention for decode: static encoder KV, no cache update."""
    B = x.shape[0]
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(B, 1, cfg.n_kv_heads,
                              cfg.n_heads // cfg.n_kv_heads, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrk", q, enc_k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(hd))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", w.astype(enc_v.dtype), enc_v,
                   preferred_element_type=jnp.float32)
    return (o.astype(x.dtype).reshape(B, 1, -1)) @ p["wo"]


# ----------------------------------------------------------------------
# Dense SwiGLU / GELU MLPs
# ----------------------------------------------------------------------

def init_mlp(key, d: int, f: int, dtype, gated: bool = True):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, f), d, dtype),
         "w_down": dense_init(ks[1], (f, d), f, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], (d, f), d, dtype)
    return p


def mlp(x, p):
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, ("dp", None, "tp"))
    return h @ p["w_down"]


# ----------------------------------------------------------------------
# Mixture of Experts (capacity-based gather dispatch, EP-shardable)
# ----------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    D, f = cfg.d_model, m.expert_d_ff
    dt = cfg.activation_dtype
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, m.num_experts), D, jnp.float32),
        "we_gate": dense_init(ks[1], (m.num_experts, D, f), D, dt),
        "we_up": dense_init(ks[2], (m.num_experts, D, f), D, dt),
        "we_down": dense_init(ks[3], (m.num_experts, f, D), f, dt),
    }
    if m.shared_experts:
        p["shared"] = init_mlp(ks[4], D, m.shared_experts * f, dt)
    return p


def moe_ffn(x: jnp.ndarray, p, m: MoEConfig,
            capacity_factor: Optional[float] = None,
            ep_exchange=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (T, D) tokens -> (out (T, D), aux_loss scalar).

    Dropping MoE: tokens are routed to ``top_k`` experts; each expert has a
    static capacity C. Dispatch/combine are gathers/scatter-adds keyed by a
    sorted slot assignment, so the expert einsums see a dense (E, C, D)
    tensor shardable on the expert axis (EP).

    ``ep_exchange`` (PR 8): an all-to-all exchange from
    :func:`repro.core.aggregators.make_exchange`, usable only inside a
    manual region where its EP axes are bound.  When set, the combine
    runs the expert-parallel wire: each EP rank scatter-adds only *its
    own expert group's* contributions (experts ``rank * ceil(E/W) ..``),
    cuts that partial output into ``W`` token blocks, and the exchange
    merges block ``r`` of every rank's partial at rank ``r`` — on the
    compressed wire the sum happens homomorphically in the sketch while
    in flight.  An ``all_gather`` of the merged blocks restores the full
    ``(T, D)`` output.  Mathematically identical to the local combine
    (every expert contribution added exactly once); float summation
    order differs, so train-level parity is allclose, not bitwise.

    The wire carries the forward value only; the *gradient* routes
    through the local combine (``local + stop_gradient(wire - local)``).
    The two are the same linear map of ``y``, so the local vjp is exact
    — and it is the only replica-consistent one in the regime the train
    step enables the wire in (full-manual regions, where expert weights
    enter replicated over the EP axes: every replica must see the
    full-slot gradient, not its group's slice scaled by the all_gather
    transpose's cross-replica sum).
    """
    T, D = x.shape
    E, K = m.num_experts, m.top_k
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    C = max(1, int(math.ceil(T * K * cf / E)))

    logits = (x.astype(jnp.float32) @ p["router"])            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, K)                          # (T, K)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    e_flat = idx.reshape(-1)                                  # (T*K,)
    t_flat = jnp.repeat(jnp.arange(T), K)
    w_flat = w.reshape(-1)
    order = jnp.argsort(e_flat)                               # stable
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    counts = jnp.bincount(e_s, length=E)                      # (E,)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - starts[e_s]
    keep = rank < C
    slot = jnp.where(keep, e_s * C + rank, E * C)             # E*C = trash slot

    gather_idx = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(
        t_s.astype(jnp.int32), mode="drop")[: E * C]
    slot_w = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, w_s, 0.0), mode="drop")[: E * C]

    xp = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    xg = xp[gather_idx].reshape(E, C, D)
    xg = constrain(xg, ("ep", None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, p["we_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xg, p["we_up"])
    h = constrain(h, ("ep", None, None))
    # §Perf iteration 4 (kimi): pin the expert *output* to EP sharding
    # too — without it GSPMD replicated the expert compute path over the
    # EP axis and paid a partial-sum all-reduce of every expert weight
    # gradient (~12 TB/device/step at 1T params).
    y = constrain(jnp.einsum("ecf,efd->ecd", h, p["we_down"]),
                  ("ep", None, None)).reshape(E * C, D)

    out = jnp.zeros((T + 1, D), jnp.float32).at[gather_idx].add(
        y.astype(jnp.float32) * slot_w[:, None])[:T]
    if ep_exchange is not None:
        from repro.core.collectives import linear_rank  # late: jax-heavy
        W = ep_exchange.workers
        rank = linear_rank(ep_exchange.ep_axes)
        group_size = -(-E // W)           # experts per EP rank group
        slot_expert = jnp.arange(E * C) // C
        mine = (slot_expert // group_size) == rank
        # partial combine: only this rank's expert group lands; other
        # groups' slots scatter to the drop row
        safe_idx = jnp.where(mine, gather_idx, T)
        partial = jnp.zeros((T + 1, D), jnp.float32).at[safe_idx].add(
            y.astype(jnp.float32) * slot_w[:, None])[:T]
        T_blk = -(-T // W)
        payload = jnp.pad(partial, ((0, W * T_blk - T), (0, 0))
                          ).reshape(W, T_blk, D)
        merged = ep_exchange(payload)     # (T_blk, D): my block, combined
        full = jax.lax.all_gather(merged, tuple(ep_exchange.ep_axes),
                                  axis=0, tiled=False)
        wire = full.reshape(W * T_blk, D)[:T]
        # wire value forward, local-combine vjp backward (see docstring);
        # when the wire is exact (W=1, or dyadic payloads) the correction
        # term is exactly zero and `out` stays bitwise the local combine
        out = out + jax.lax.stop_gradient(wire - out)
    out = out.astype(x.dtype)

    if m.shared_experts:
        out = out + mlp(x, p["shared"])

    # Switch-style load-balance aux loss.
    frac = counts.astype(jnp.float32) / jnp.maximum(T * K, 1)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return out, aux
