"""The check that decides ``correct``, on the CPU at a small size: a
sound run passes; the control and every fault a training cell can have
fail it. Faults are planted in the timed path underneath a whole run
(the harness's look for a chip skipped)."""

import json
import math

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.harness import cell as cell_lib, program
from chipbench.harness.traffic import LMTraffic
from chipbench.tests import cells

SEED = 2**31 + 11


def drive(name, tamper=None, capsys=None):
    rc = run.main(["--workload", name, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0"], require_tpu=False, tamper=tamper,
                  cell_spec=cells.cell(name))
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", list(cells.CELLS))
def test_sound_run_is_correct(name, capsys):
    out = drive(name, capsys=capsys)
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", list(cells.CELLS))
def test_control_fails(name):
    cell = cells.cell(name)
    devices = jax.devices()[:cell["chips"]]
    prog = program.Program(cell, devices)
    mix = LMTraffic(cell["traffic"], cell["config"]["model"]["vocab_size"], SEED)
    params = lambda: prog.draw(program.seed_key(SEED))
    ref = cell_lib.reference_readings(cell_lib.reference(cell, devices), mix, params())
    ctl = cell_lib.reference_readings(cell_lib.reference(cell, devices, mode="fp8"), mix, params())
    numbers = cell_lib.compare(ctl, ref)
    assert any(v > cell["limits"][k]["limit"] for k, v in numbers.items())


def _unchanged(prog):
    step = prog.step

    def keep(state, batch):
        old = jax.tree.map(jnp.copy, state)
        _, met = step(state, batch)
        return old, met
    prog.step = keep


def _half_batch(prog):
    step, accum = prog.step, prog.train.accum_steps
    ranks = prog.mesh.shape["data"]

    def half(state, batch):
        def cut(x):
            x = x.reshape((ranks, accum, -1) + x.shape[1:])[:, : accum // 2]
            return jnp.concatenate([x, x], axis=1).reshape((-1,) + x.shape[3:])
        return step(state, prog.put(jax.tree.map(cut, batch)))
    prog.step = half


def _altered(prog):
    step = prog.step

    def alter(state, batch):
        state, met = step(state, batch)
        opt = dict(state.opt, m=jax.tree.map(lambda a: a * 1.5, state.opt["m"]))
        return type(state)(params=state.params, opt=opt, residual=state.residual,
                           step=state.step), met
    prog.step = alter


def _no_exchange(monkeypatch):
    from repro.core import aggregators
    monkeypatch.setattr(jax.lax, "psum", lambda x, axes, **k: x)
    monkeypatch.setattr(aggregators, "or_allreduce", lambda w, axes, **k: w)


@pytest.mark.parametrize("fault,name", [
    ("unchanged", "tiny-dense.dp1"), ("half_batch", "tiny-dense.dp1"),
    ("altered", "tiny-dense.dp1"), ("half_batch", "tiny-dense32.dp4"),
    ("no_exchange", "tiny-dense32.dp4"), ("unchanged", "tiny-ssm.dp1"),
])
def test_fault_makes_run_incorrect(fault, name, capsys, monkeypatch):
    if fault == "no_exchange":
        _no_exchange(monkeypatch)
        tamper = None
    else:
        tamper = {"unchanged": _unchanged, "half_batch": _half_batch,
                  "altered": _altered}[fault]
    out = drive(name, tamper=tamper, capsys=capsys)
    assert out["correct"] is False
    assert any(not math.isfinite(c["value"]) or c["value"] > c["limit"]
               for c in out["compared"].values())
