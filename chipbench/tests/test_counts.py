"""FLOP and byte counts against hand counts at the published widths."""

import json
import os

import pytest

from chipbench.families import dense, ssm
from chipbench.harness import counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_granite_flops_by_hand():
    m = load("granite-3-2b.4L")["model"]
    # per layer: q 2048x2048, k and v 2048x512 each, o 2048x2048, MLP 3 x 2048x8192
    layer = 2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192
    assert layer == 60_817_408
    head = 2048 * 49155
    assert dense.matmul_params(m) == 4 * layer + head == 343_939_072
    # attention: 4 layers x 6 x 32 heads x 64 x (4096 + 1)
    attn = 4 * 6 * 2048 * 4097
    assert dense.flops_per_token(m, 4096) == 6 * 343_939_072 + attn
    assert dense.flops_per_token(m, 4096) == pytest.approx(2.2650e9, rel=1e-4)


def test_mamba_flops_by_hand():
    m = load("mamba2-1.3b.16L")["model"]
    # d_inner 4096, 64 heads of 64, state 128
    layer = 2 * 2048 * 4096 + 2 * 2048 * 128 + 2048 * 64 + 4096 * 2048
    assert layer == 25_821_184
    assert ssm.matmul_params(m) == 16 * layer + 2048 * 50277
    half = 257 / 2
    per_layer = (2 * 128 * half + 2 * 64 * 64 * half + 4 * 64 * 64 * 128
                 + 2 * 4 * (4096 + 256))
    assert ssm.flops_per_token(m, 4096) == 6 * ssm.matmul_params(m) + 3 * 16 * per_layer
    assert ssm.flops_per_token(m, 4096) == pytest.approx(3.2511e9, rel=1e-4)


def test_codec_bytes_by_hand():
    c = load("granite-3-2b.4L")["train"]["compression"]
    n = 30720 * 10 - 5             # ten blocks of 60 x 512, the last not full
    elems = 30720 * 10
    p = counts.producer(n, c)
    assert p == 4 * elems + 4 * 10 * 6 * 512 + elems / 8 + 4 * 10
    q = counts.consumer(n, c)
    assert q == 4 * 10 * 6 * 512 + elems / 8 + 5 * elems
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_share(p, p / 819e9 * 2, peaks) == pytest.approx(50.0)
