"""What the reference holds on the device, read from the compiled
programs on the CPU at a small size: the update writes in place, and the
gradient pass is given the float32 parameters alone, never the moments."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import cell as cell_lib, program
from chipbench.harness.traffic import LMTraffic
from chipbench.reference import train as ref_train
from chipbench.tests import cells


def _nbytes(tree):
    return sum(x.size * np.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))


def _setup(name):
    cell = cells.cell(name)
    devices = jax.devices()[:cell["chips"]]
    shape = program.Program(cell, devices).state_shape.params
    return cell, devices, shape, cell_lib.reference(cell, devices)


@pytest.mark.parametrize("name", list(cells.CELLS))
def test_update_writes_in_place(name):
    cell, _, shape, ref = _setup(name)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), shape)
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shape)
    opt = ref_train._Opt(cell["config"]["train"]["optimizer"])
    mem = ref._adamw.lower(params, f32, f32, f32, 1e-4, 201.0, opt, 1.0).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(params) + 2 * _nbytes(f32)


@pytest.mark.parametrize("name", list(cells.CELLS))
def test_gradient_pass_takes_no_moments(name):
    cell, _, shape, ref = _setup(name)
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shape)
    mix = cell["traffic"]
    toks = jax.ShapeDtypeStruct((mix["ranks"] * mix["seqs_per_rank"], mix["seq_len"]), jnp.int32)
    mem = ref._grads.lower(f32, toks, toks).compile().memory_analysis()
    # per device: the parameters whole, the tokens and labels of its rank
    assert mem.argument_size_in_bytes == _nbytes(f32) + 2 * _nbytes(toks) // mix["ranks"]


def test_state_between_steps_is_on_the_host():
    cell, devices, _, ref = _setup("tiny-dense32.dp4")
    prog = program.Program(cell, devices)
    params = jax.device_get(prog.draw(program.seed_key(3)))
    mix = LMTraffic(cell["traffic"], cell["config"]["model"]["vocab_size"], 3)
    st, _, _ = ref.step(ref.init(params), mix.batch(0))
    for k in ("params", "m", "v"):
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(st[k])), k
    assert set(ref.seconds) == {"gradients", "pack", "decode", "update"}
