"""The plain reference agrees with the program where both are exact:
the same sums in float32 on the CPU, at a small size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import program, spec
from chipbench.reference import codec, train as ref_train
from chipbench.reference.precision import Dot
from chipbench.tests import cells

program.import_program()
from repro.core.config import CompressionConfig  # noqa: E402
from repro.core.peeling import peel_blocks  # noqa: E402
from repro.core.sketch import encode_blocks  # noqa: E402
from repro.core import topk  # noqa: E402
from repro.models import model_api  # noqa: E402


@pytest.mark.parametrize("ratio", [0.1, 0.5])
def test_codec_matches_program_oracle(ratio):
    cfg = CompressionConfig(ratio=ratio, topk_ratio=0.04)
    geo = codec.Geometry(dict(rows=6, lanes=512, rounds=10, seed=cfg.seed, ratio=ratio))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, cfg.group, cfg.lanes)).astype(np.float32)
    for b, d in enumerate([0.02, 0.05, 0.1, 0.3, 0.6]):
        x[b] *= rng.random(x[b].shape) < d
    ids = jnp.arange(5, dtype=jnp.int32) + 777
    sk = encode_blocks(jnp.asarray(x), ids, cfg)
    np.testing.assert_allclose(codec.encode(jnp.asarray(x), ids, geo), sk, atol=1e-5)
    want = peel_blocks(sk, jnp.asarray(x != 0), ids, cfg)
    vals, left = codec.decode(sk, jnp.asarray(x != 0), ids, geo)
    assert bool(jnp.all(left == want.residual)) and int(left.sum()) > 0
    np.testing.assert_allclose(vals, want.values, atol=1e-5)


def test_topk_matches_program_rule():
    g = jax.random.normal(jax.random.PRNGKey(1), (100_003,))
    r = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (100_003,))
    sparse, res = ref_train._topk_ef(g, r, 0.04)
    want, want_res = topk.apply_error_feedback(g, r, int(100_003 * 0.04), exact=False)
    np.testing.assert_array_equal(sparse, want)
    np.testing.assert_array_equal(res, want_res)


@pytest.mark.parametrize("name", ["tiny-dense32.dp4", "tiny-ssm.dp1"])
def test_model_loss_matches_program(name):
    cell = cells.cell(name)
    cfg = cell["config"]
    fam = spec.family(cfg)
    prog = program.Program(cell, jax.devices()[:cell["chips"]])
    model = dataclasses.replace(prog.model, dtype="float32")
    api = model_api(model)
    params = prog.draw(jax.random.PRNGKey(3))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 256)
    labs = jnp.roll(toks, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        want, _ = api.loss(params, {"tokens": toks, "labels": labs})
    got = jnp.mean(jax.vmap(lambda a, b: fam.sequence_loss(
        params, a, b, cfg["model"], Dot("f32")))(toks, labs))
    assert abs(float(got) - float(want)) < 1e-5


def test_codec_stopping_sets_match_program_oracle():
    """The registry geometry on blocks near and above the peeling
    threshold: some under capacity end in a stopping set, some stop at
    the round cap, some are over capacity. Values and the unpeeled mask
    agree with the program's peel."""
    cfg = CompressionConfig(ratio=0.1, topk_ratio=0.04)
    geo = codec.Geometry(dict(rows=6, lanes=512, rounds=10, seed=cfg.seed, ratio=0.1))
    rng = np.random.default_rng(0)
    nnz = list(np.linspace(1800, 2600, 16).astype(int)) + [6000, 12000]
    x = np.zeros((len(nnz), cfg.group * cfg.lanes), np.float32)
    for b, k in enumerate(nnz):
        at = rng.choice(x.shape[1], k, replace=False)
        x[b, at] = rng.normal(size=k)
    x = jnp.asarray(x.reshape(len(nnz), cfg.group, cfg.lanes))
    bits, ids = x != 0, jnp.arange(len(nnz), dtype=jnp.int32) + 5
    sk = encode_blocks(x, ids, cfg)
    want = peel_blocks(sk, bits, ids, cfg)
    vals, left = codec.decode(sk, bits, ids, geo)
    assert bool(jnp.all(left == want.residual))
    np.testing.assert_allclose(vals, want.values, atol=1e-5)
    # a block under capacity (rows * lanes / 1.23) that no number of rounds peels
    stuck = codec.decode(sk, bits, ids, codec.Geometry(
        dict(rows=6, lanes=512, rounds=200, seed=cfg.seed, ratio=0.1)))[1].sum((1, 2))
    cap = 6 * 512 / 1.23
    assert any(int(s) > 0 and k < cap for s, k in zip(stuck, nnz))
