"""The train step names its phases and layers with ``jax.named_scope``,
and the names survive compilation: each must appear on the ``op_name``
stacks of the compiled HLO (``chipbench/harness/scopes.py`` joins them
to a chip trace). The step is compiled in a subprocess driver on two
CPU devices, so that the compressed wires run the codec's stages."""
import json
import os
import subprocess
import sys

import pytest

DRIVERS = os.path.join(os.path.dirname(__file__), "drivers")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CODEC = ("pack", "encode", "reduce", "peel", "unpack")
EXPECTED = (
    [("tiny-dense", s) for s in ("embed", "attention", "mlp", "head_loss",
                                 "optimizer", "aggregate")]
    + [("tiny-dense", m) for m in ("forward", "transpose(", "rematted_computation")]
    + [("tiny-ssm", s) for s in ("mamba", "ssd_scan")]
    + [("tiny-dense32.dp2.compressed", s) for s in CODEC]
    + [("tiny-dense32.dp2.compressed_rs", s) for s in CODEC])


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                        "--xla_disable_hlo_passes=all-reduce-promotion")
    r = subprocess.run([sys.executable, os.path.join(DRIVERS, "scopes_driver.py")],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"scopes_driver failed:\n{r.stdout}\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case,name", EXPECTED, ids=lambda x: x)
def test_scope_survives_compilation(found, case, name):
    got = found[case]
    if name in ("forward", "transpose(", "rematted_computation"):
        assert name in got["marks"]
    else:
        assert name in got["stack"]


def test_training_loop_spans_in_profiler_trace(tmp_path):
    """Each step of ``run_training`` is a ``train`` step annotation with
    its batch, dispatch, read-back and checkpoint spans on the host."""
    import glob
    import jax
    from jax.profiler import ProfileData
    from repro.compat import make_mesh
    from repro.models import ModelConfig, model_api
    from repro.parallel.sharding import ShardingProfile
    from repro.train import OptimizerConfig, TrainConfig
    from repro.train.loop import run_training

    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=1, d_ff=64, vocab=64, dtype="float32")
    tc = TrainConfig(aggregator="dense", remat="none",
                     optimizer=OptimizerConfig(warmup_steps=1, total_steps=10),
                     sharding=ShardingProfile(zero1=False))
    with jax.profiler.trace(str(tmp_path / "trace")):
        run_training(model_api(cfg), tc, make_mesh((1, 1), ("data", "model")),
                     global_batch=2, seq_len=8, steps=2, log_every=0,
                     ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=1)
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines for e in line.events]
    for span in ("train.batch", "train.dispatch", "train.readback", "train.checkpoint"):
        assert names.count(span) == 2, span
    assert names.count("train") == 2
