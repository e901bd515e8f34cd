"""The four Pallas kernels compile for a TPU v5e that is described, not
attached: the chip's compiler runs here and refuses what Mosaic cannot
lower or what does not fit the chip's memory — which interpret mode
never checks. Default geometry (G=60, c=512, rows=6), one default
bucket (``bucket_bytes`` 4 MiB), f32 and fxp32 wires.

Only the worker given this file loads the TPU compiler: the topology is
described inside a module fixture, never at import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CompressionConfig
from repro.kernels import (sketch_encode_pallas, sketch_peel_pallas,
                           encode_pack_quantize_pallas,
                           dequant_peel_unpack_pallas)
from repro.net.fixedpoint import FixedPointWire

CFG = CompressionConfig()
# One default bucket of a large stream: 4 MiB rounded to whole blocks.
NB = CFG.bucket_elems_for(1 << 30) // CFG.block_elems
MBITS = FixedPointWire(workers=4).mantissa_bits


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip cannot be read back from the
        # persistent cache: keep the cache out of these tests.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, wire, sh):
    """(fn, abstract args) for one kernel on one wire."""
    G, c, R = CFG.group, CFG.lanes, CFG.rows
    x = _arg((NB, G, c), jnp.float32, sh)
    ids = _arg((NB,), jnp.int32, sh)
    exps = _arg((NB,), jnp.int32, sh)
    words = _arg((NB, CFG.block_elems // 32), jnp.uint32, sh)
    sk_dtype = jnp.int32 if wire == "fxp32" else jnp.float32
    sk = _arg((NB, R, c), sk_dtype, sh)
    q = dict(mantissa_bits=MBITS, interpret=False)
    if name == "encode":
        return (lambda x, i: sketch_encode_pallas(x, i, CFG, False),
                (x, ids))
    if name == "peel":
        bits = _arg((NB, G, c), jnp.bool_, sh)
        return (lambda y, b, i: sketch_peel_pallas(y, b, i, CFG, False),
                (_arg((NB, R, c), jnp.float32, sh), bits, ids))
    if name == "producer":
        if wire == "f32":
            return (lambda x, i: encode_pack_quantize_pallas(
                x, i, CFG, interpret=False), (x, ids))
        return (lambda x, i, e: encode_pack_quantize_pallas(
            x, i, CFG, exponents=e, **q), (x, ids, exps))
    if wire == "f32":
        return (lambda s, w, i: dequant_peel_unpack_pallas(
            s, w, i, CFG, interpret=False), (sk, words, ids))
    return (lambda s, w, i, e: dequant_peel_unpack_pallas(
        s, w, i, CFG, exponents=e, **q), (sk, words, ids, exps))


@pytest.mark.parametrize("name,wire", [
    ("encode", "f32"), ("peel", "f32"),
    ("producer", "f32"), ("producer", "fxp32"),
    ("consumer", "f32"), ("consumer", "fxp32")])
def test_kernel_compiles_for_v5e(one_chip, name, wire):
    fn, args = _kernel_case(name, wire, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the kernel's operands and results fit the chip's 16 GiB with room
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("causal", [True, False])
def test_attention_kernel_compiles_for_v5e(one_chip, causal):
    """Granite's training attention (B 1, S 4096, 32 heads over 8 KV
    heads, hd 64, bf16) through the fused kernel, forward and backward:
    one forward kernel and one fused backward kernel, no blockwise loop."""
    from benchmarks import attention_kernels
    from repro.models import layers as L
    blocks = L.attention_kernel_blocks(4096, 4096, 64, 0, "tpu", 1)
    q = _arg((1, 4096, 32, 64), jnp.bfloat16, one_chip)
    kv = _arg((1, 4096, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = L.kernel_attention(q, k, v, causal, blocks)
        return jnp.sum(o.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    assert attention_kernels.count(compiled.as_text()) == {
        "kernel_calls": 2, "blockwise_calls": 0,
        "kernel_by_name": {"splash_mha_fwd_residuals": 1,
                           "splash_mha_dkv_no_residuals": 1}}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30
