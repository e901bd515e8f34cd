"""Attention for training and prefill (``repro.models.layers``).

The blockwise path against a plain float32 softmax attention, values and
gradients; the rule that picks the fused TPU kernel instead
(``attention_kernel_blocks``) and what it observes of the mesh; and the
kernel path itself, run by Pallas's TPU interpreter on the CPU, against
the blockwise path: alone, and inside the train step on a 2-rank
data-parallel mesh.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro import compat
from repro.models import layers as L


def reference_attention(q, k, v, causal, q_offset=0):
    """Softmax attention in float32 at HIGHEST precision, one head group
    at a time. q (B, Sq, H, hd); k, v (B, Skv, KV, hd)."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / math.sqrt(q.shape[-1])
    if causal:
        rows = q_offset + jnp.arange(q.shape[1])[:, None]
        s = jnp.where(rows >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision=hi)


def _inputs(b, sq, skv, h, kv, hd, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, sq, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, skv, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, skv, kv, hd), jnp.float32)
    w = jax.random.normal(ks[3], (b, sq, h, hd), jnp.float32)
    return q, k, v, w


def _value_and_grads(attn, q, k, v, w):
    """The output and d(sum(out * w))/d(q, k, v)."""
    def run(q, k, v, w):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out,) + vjp(w)
    return jax.jit(run)(q, k, v, w)


def _assert_close(got, want, rtol):
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        gap = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))
        assert gap < rtol, f"{name}: relative gap {gap:.2e}"


# (causal, Sq, Skv, H, KV, q_block, kv_block, q_offset)
BLOCKWISE_CASES = {
    "causal": (True, 64, 64, 4, 4, 16, 32, 0),
    "not_causal": (False, 64, 64, 4, 4, 16, 32, 0),
    "gqa_rep4_causal": (True, 64, 64, 8, 2, 32, 16, 0),
    "gqa_rep4_not_causal": (False, 48, 48, 8, 2, 16, 16, 0),
    "ragged_causal": (True, 50, 50, 4, 2, 16, 24, 0),
    "ragged_not_causal": (False, 37, 53, 4, 2, 16, 24, 0),
    "q_offset": (True, 16, 48, 4, 2, 8, 16, 32),
    "q_offset_ragged": (True, 9, 41, 8, 2, 4, 16, 32),
    "cross": (False, 24, 40, 4, 4, 16, 16, 0),
}


@pytest.mark.parametrize("case", list(BLOCKWISE_CASES))
def test_blockwise_matches_reference(case):
    causal, sq, skv, h, kv, qb, kb, off = BLOCKWISE_CASES[case]
    q, k, v, w = _inputs(2, sq, skv, h, kv, 16)
    got = _value_and_grads(
        lambda q, k, v: L.blockwise_attention(q, k, v, causal, qb, kb, off),
        q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal, off), q, k, v, w)
    _assert_close(got, want, 1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_on_cpu_is_blockwise(causal):
    q, k, v, _ = _inputs(1, 64, 64, 4, 2, 16)
    got = L.flash_attention(q, k, v, causal, q_block=16, kv_block=32)
    want = L.blockwise_attention(q, k, v, causal, 16, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


GRANITE_TRAIN = dict(sq=4096, skv=4096, hd=64, q_offset=0, backend="tpu",
                     auto_devices=1)

DISPATCH_CASES = {
    "granite_train_tpu_tp1": ({}, True),
    "head_dim_128_inexact_scale": (dict(hd=128), False),
    "cpu": (dict(backend="cpu"), False),
    "tp2": (dict(auto_devices=2), False),
    "cache_offset": (dict(sq=1024, q_offset=3072), False),
    "prefill_with_cache_offset_equal_lengths": (dict(q_offset=4096), False),
    "cross_attention": (dict(sq=448, skv=1500), False),
    "whisper_encoder_1500": (dict(sq=1500, skv=1500), False),
    "ragged_length": (dict(sq=4000, skv=4000), False),
    "shorter_than_a_block": (dict(sq=64, skv=64), False),
    "head_dim_80": (dict(hd=80), False),
}


@pytest.mark.parametrize("case", list(DISPATCH_CASES))
def test_kernel_dispatch(case):
    change, engages = DISPATCH_CASES[case]
    kw = dict(GRANITE_TRAIN, **change)
    blocks = L.attention_kernel_blocks(**kw)
    assert (blocks is not None) == engages
    if engages:
        for b in (blocks.block_q, blocks.block_kv, blocks.block_kv_compute,
                  blocks.block_q_dkv, blocks.block_kv_dkv):
            assert b >= 128 and kw["sq"] % b == 0


def _mesh(shape, types):
    return AbstractMesh(shape, ("data", "model"), axis_types=types)


OBSERVED_CASES = {
    "no_mesh": (None, 1),
    "data_manual_model_1": (_mesh((4, 1), (AxisType.Manual, AxisType.Auto)), 1),
    "data_manual_model_2": (_mesh((2, 2), (AxisType.Manual, AxisType.Auto)), 2),
    "all_manual": (_mesh((2, 2), (AxisType.Manual, AxisType.Manual)), 1),
    "all_auto": (_mesh((2, 1), (AxisType.Auto, AxisType.Auto)), 2),
}


@pytest.mark.parametrize("case", list(OBSERVED_CASES))
def test_auto_devices_observed(case):
    mesh, want = OBSERVED_CASES[case]
    if mesh is None:
        assert compat.auto_devices() == want
        return
    with jax.sharding.use_abstract_mesh(mesh):
        assert compat.auto_devices() == want


@pytest.mark.parametrize("causal", [True, False])
def test_kernel_path_matches_blockwise(causal):
    """The kernel path (GQA expansion, head transposes, the bundled
    kernel's custom VJP) in Pallas's TPU interpreter, at the smallest
    length the kernel takes with two blocks along each axis."""
    from jax.experimental.pallas import tpu as pltpu
    s, hd = 256, 64
    q, k, v, w = _inputs(1, s, s, 4, 2, hd, seed=1)
    blocks = dataclasses.replace(
        L.attention_kernel_blocks(s, s, hd, 0, "tpu", 1), block_q=128,
        block_kv=128, block_kv_compute=128, block_q_dkv=128,
        block_kv_dkv=128, block_kv_dkv_compute=128)
    with pltpu.force_tpu_interpret_mode():
        got = _value_and_grads(
            lambda q, k, v: L.kernel_attention(q, k, v, causal, blocks),
            q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: L.blockwise_attention(q, k, v, causal, 64), q, k, v, w)
    _assert_close(got, want, 1e-4)


def test_lowering_count_on_cpu():
    """The compiled program's attention, counted from its HLO text as
    ``benchmarks/attention_kernels.py`` counts a chip run's: on the CPU
    every call is blockwise, a forward loop and its transpose's."""
    from benchmarks import attention_kernels
    q, k, v, w = _inputs(1, 64, 64, 4, 2, 16)
    attn = lambda q, k, v: L.flash_attention(q, k, v, True, 16, 32)
    text = jax.jit(lambda *a: _value_and_grads(attn, *a)).lower(
        q, k, v, w).compile().as_text()
    assert attention_kernels.count(text) == {
        "kernel_calls": 0, "kernel_by_name": {}, "blockwise_calls": 2}


DP_AGGREGATORS = ("dense", "compressed", "compressed_rs")


@pytest.fixture(scope="module")
def data_parallel_runs():
    """``tests/drivers/attention_dp_driver.py`` on two CPU devices."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(here, "..", "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        "--xla_disable_hlo_passes=all-reduce-promotion")
    r = subprocess.run(
        [sys.executable, os.path.join(here, "drivers", "attention_dp_driver.py"),
         *DP_AGGREGATORS], capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"attention_dp_driver failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("aggregator", DP_AGGREGATORS)
def test_kernel_path_in_data_parallel_step(data_parallel_runs, aggregator):
    """With the dispatch told it is on a TPU, the train step on a 2-rank
    mesh (``data`` manual, ``model`` 1 auto) takes the kernel for every
    layer's attention, in the nested per-device region, and trains as the
    blockwise path does: the same losses, and weight changes within 1e-4
    of the largest."""
    got = data_parallel_runs[aggregator]
    assert got["blockwise"]["calls"]["kernel"] == 0
    assert got["blockwise"]["calls"]["blockwise"] > 0
    assert got["kernel"]["calls"]["blockwise"] == 0
    assert got["kernel"]["calls"]["kernel"] > 0
    np.testing.assert_allclose(got["kernel"]["loss"], got["blockwise"]["loss"],
                               rtol=1e-5)
    assert got["blockwise"]["loss"][-1] < got["blockwise"]["loss"][0]
    assert got["param_gap"] < 1e-4
