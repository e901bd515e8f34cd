#!/usr/bin/env python3
"""Time granite's training attention on a TPU: the bundled Pallas flash
and splash kernels over a grid of block sizes, against the repo's
blockwise path. The sweep behind ``models/layers.py``'s kernel choice and
its block sizes (``_KERNEL_BLOCK``, ``_KERNEL_KV_COMPUTE``).

    python benchmarks/attention_sweep.py            # on the chip: times
    python benchmarks/attention_sweep.py --gaps     # and each kernel's gap
    JAX_PLATFORMS=cpu python benchmarks/attention_sweep.py --described

Shapes: B 1, S 4096, 32 heads over 8 KV heads, hd 64, bfloat16, causal,
KV heads expanded as the program does; the kernels take (B, H, S, hd).
One JSON line per case: ``fwd_ms`` and ``fwd_bwd_ms`` (the mean of
``--iters`` calls after one warm call), or ``ok`` false and the error
(a block too large for the kernel's fast memory). ``--gaps`` adds, for
the blockwise path and the fastest flash and splash settings, the
largest relative gap of the output and the q, k, v gradients (k and v
before expansion) to a float32 softmax at HIGHEST precision.
``--described`` compiles every case for a described v5e instead and
reports the backward's temporary bytes: nothing runs, so it gives no
time.
"""

import argparse
import functools
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas.ops.tpu import flash_attention as fa  # noqa: E402
from jax.experimental.pallas.ops.tpu import splash_attention as sp  # noqa: E402

B, H, KV, S, D = 1, 32, 8, 4096, 64
SCALE = 1 / math.sqrt(D)

# flash: (block_q, block_k_major, block_k), then the dkv and dq blocks
FLASH_DKV, FLASH_DQ = (512, 512, 512, 512), (512, 512, 512)
FLASH_GRID = (
    [((128, 128, 128), (128, 128, 128, 128), (128, 128, 128))]
    + [(fwd, FLASH_DKV, FLASH_DQ) for fwd in (
        (256, 512, 256), (512, 512, 512), (512, 1024, 512), (1024, 1024, 512),
        (512, 2048, 512), (1024, 512, 512), (256, 1024, 512))]
    + [((512, 1024, 512), dkv, FLASH_DQ) for dkv in (
        (1024, 512, 1024, 512), (512, 256, 1024, 256), (1024, 1024, 512, 512),
        (256, 256, 512, 512), (1024, 512, 512, 512), (512, 512, 1024, 1024))]
    + [((512, 1024, 512), FLASH_DKV, dq) for dq in (
        (1024, 512, 512), (512, 1024, 512), (256, 512, 512), (512, 256, 256),
        (1024, 1024, 512), (512, 128, 128))]
    + [((512, 1024, 512), (512, 512, 1024, 1024), (1024, 512, 512)),
       ((512, 512, 512), (1024, 512, 1024, 512), (1024, 512, 512))])
# splash: (block_q, block_kv, block_kv_compute), the same for dkv
SPLASH_GRID = ((512, 512, 512), (512, 1024, 512), (1024, 512, 512),
               (1024, 1024, 512), (1024, 1024, 1024), (2048, 1024, 512),
               (1024, 2048, 512), (2048, 2048, 512))


def flash_blocks(fwd, dkv, dq):
    return fa.BlockSizes(
        block_q=fwd[0], block_k_major=fwd[1], block_k=fwd[2], block_b=1,
        block_q_major_dkv=dkv[0], block_q_dkv=dkv[1],
        block_k_major_dkv=dkv[2], block_k_dkv=dkv[3],
        block_q_dq=dq[0], block_k_major_dq=dq[1], block_k_dq=dq[2])


def flash(fwd, dkv, dq):
    blocks = flash_blocks(fwd, dkv, dq)
    return lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, sm_scale=SCALE, block_sizes=blocks)


def splash_blocks(bq, bkv, compute, fused=True):
    kw = dict(block_q=bq, block_kv=bkv, block_kv_compute=compute,
              block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=compute,
              use_fused_bwd_kernel=fused)
    if not fused:
        kw.update(block_q_dq=bq, block_kv_dq=bkv)
    return sp.BlockSizes(**kw)


def splash_mha(blocks):
    mask = sp.MultiHeadMask([sp.CausalMask((S, S))] * H)
    kernel = sp.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                q_seq_shards=1)
    return lambda q, k, v: jax.vmap(kernel)(q * SCALE, k, v)


def splash_mqa(blocks):
    """Each KV head's query group through the MQA kernel, on the KV heads
    before expansion."""
    rep = H // KV
    mask = sp.MultiHeadMask([sp.CausalMask((S, S))] * rep)
    kernel = sp.make_splash_mqa(mask, block_sizes=blocks, head_shards=1,
                                q_seq_shards=1)

    def run(q, k, v):
        qg = (q * SCALE).reshape(B, KV, rep, S, D)
        o = jax.vmap(jax.vmap(kernel))(qg, k[:, ::rep], v[:, ::rep])
        return o.reshape(B, H, S, D)
    return run


def blockwise(q, k, v):
    from repro.models import layers as L
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(L.blockwise_attention(t(q), t(k), t(v), True, 512))


def reference(q, k, v):
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) * SCALE
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)


def value_and_grads(attn, q, k, v, g):
    o, vjp = jax.vjp(attn, q, k, v)
    return (o,) + vjp(g.astype(o.dtype))


def inputs(seed):
    """q, k and v over the KV heads, and an output cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16) for kk in (ks[0], ks[3]))
    k, v = (jax.random.normal(kk, (B, KV, S, D), jnp.bfloat16) for kk in ks[1:3])
    return q, k, v, g


def expand(x):
    return x.repeat(H // KV, axis=1)


def timed(fn, args, iters):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / iters * 1e3


def case(name, attn, iters, described):
    fb = functools.partial(value_and_grads, attn)
    rec = {"name": name}
    try:
        if described:
            x = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16, sharding=described)
            jax.jit(attn).lower(x, x, x).compile()
            mem = jax.jit(fb).lower(x, x, x, x).compile().memory_analysis()
            rec.update(ok=True, bwd_temp_mib=mem.temp_size_in_bytes / 2**20)
        else:
            q, k, v, g = inputs(0)
            k, v = expand(k), expand(v)
            rec.update(fwd_ms=timed(jax.jit(attn), (q, k, v), iters),
                       fwd_bwd_ms=timed(jax.jit(fb), (q, k, v, g), iters), ok=True)
    except Exception as e:  # a block the kernel cannot hold: a result too
        rec.update(ok=False, err=f"{type(e).__name__}: {str(e)[:300]}")
    print(json.dumps(rec), flush=True)


def gaps(name, attn):
    """Gradients taken with respect to the KV heads before expansion."""
    on_kv = lambda f: lambda q, k, v: f(q, expand(k), expand(v))
    q, k, v, g = inputs(1)
    got = jax.jit(functools.partial(value_and_grads, on_kv(attn)))(q, k, v, g)
    f32 = (x.astype(jnp.float32) for x in (q, k, v, g))
    want = jax.jit(functools.partial(value_and_grads, on_kv(reference)))(*f32)
    rel = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))
           for a, b in zip(got, want)]
    print(json.dumps({"name": name, "rel_gap_out_dq_dk_dv": rel}), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--gaps", action="store_true")
    ap.add_argument("--described", action="store_true")
    args = ap.parse_args(argv)
    described = None
    if args.described:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        described = SingleDeviceSharding(topo.devices[0])
    print(json.dumps({"device": "described v5e" if described
                      else jax.devices()[0].device_kind}), flush=True)
    for fwd, dkv, dq in FLASH_GRID:
        case(f"flash fwd{fwd} dkv{dkv} dq{dq}", flash(fwd, dkv, dq),
             args.iters, described)
    for bq, bkv, c in SPLASH_GRID:
        case(f"splash mha fused q{bq} kv{bkv} c{c}",
             splash_mha(splash_blocks(bq, bkv, c)), args.iters, described)
    for b in (512, 1024):
        case(f"splash mha q{b} kv{b} c512",
             splash_mha(splash_blocks(b, b, 512, fused=False)), args.iters, described)
    for bq, bkv, c in ((1024, 1024, 512), (2048, 1024, 512), (1024, 2048, 512)):
        case(f"splash mqa fused q{bq} kv{bkv} c{c}",
             splash_mqa(splash_blocks(bq, bkv, c)), args.iters, described)
    case("blockwise q512 kv1024", blockwise, max(1, args.iters // 4), described)
    if args.gaps and not described:
        gaps("blockwise", blockwise)
        gaps("flash fwd(512, 1024, 512)",
             flash((512, 1024, 512), (512, 512, 1024, 1024), (1024, 512, 512)))
        gaps("splash mha fused q1024 kv1024 c512", splash_mha(splash_blocks(1024, 1024, 512)))
        gaps("splash mqa fused q1024 kv1024 c512", splash_mqa(splash_blocks(1024, 1024, 512)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
