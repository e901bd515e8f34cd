"""The system under test, as the benchmark drives it.

Builds the program's jitted train step the way its training loop does
(``repro.train.step.build_train_step`` with the shardings of
``state_specs``/``batch_specs``, state donated), from a configuration
file: the registry entry named in ``program.arch``, its fields replaced
by the file's numbers, and the training semantics the file pins
(aggregator, microbatches, optimizer, codec). Performance settings are
left to the program's defaults.

The weights are the benchmark's own: drawn from the seed on the device
in one jitted call, in the dtypes the program stores them in, and
placed into an otherwise zero train state (zero moments, zero
error-feedback residuals) whose step is the end of the warm-up, so the
steps the check reads run at the full learning rate. The reference
starts from the same draw and the same step.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import sys
import zlib

import jax
import jax.numpy as jnp

from chipbench.harness import spec as spec_lib


def import_program(root=spec_lib.ROOT):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise spec_lib.SpecError("no program (src/repro) beside the benchmark")
    if src not in sys.path:
        sys.path.insert(0, src)


def seed_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def draw_params(key, params_shape, family):
    """The benchmark's weights: each leaf drawn by the family's ``draw``
    from a key folded with the leaf's path, in the leaf's dtype."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params_shape)
    leaves = []
    for p, leaf in flat:
        path = leaf_path(p)
        k = jax.random.fold_in(key, zlib.crc32(path.encode()))
        x = family.draw(k, path.split("/")[-1], leaf.shape)
        leaves.append(x.astype(leaf.dtype))
    return jax.tree.unflatten(tdef, leaves)


def leaf_path(p):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


class Program:
    """The compiled step, its shardings and the state it starts from."""

    def __init__(self, cell, devices):
        import_program()
        from repro import compat
        from repro.configs import get_arch
        from repro.models import model_api
        from repro.train import step as step_lib

        cfg, mix = cell["config"], cell["traffic"]
        fam = spec_lib.family(cfg)
        arch = get_arch(cfg["program"]["arch"])
        m = cfg["model"]
        self.model = dataclasses.replace(arch.model, **fam.model_fields(m, arch.model))
        t = cfg["train"]
        self.train = dataclasses.replace(
            arch.train, aggregator=t["aggregator"], accum_steps=t["accum_steps"],
            optimizer=dataclasses.replace(arch.train.optimizer, **t["optimizer"]),
            compression=dataclasses.replace(arch.train.compression, **t["compression"]))
        ranks = mix["ranks"]
        self.mesh = compat.make_mesh((ranks, 1), ("data", "model"), devices=devices[:ranks])
        api = model_api(self.model)
        init = functools.partial(step_lib.init_train_state, api, self.train, self.mesh)
        shape = jax.eval_shape(init, jax.random.PRNGKey(0))
        step_fn, specs = step_lib.build_train_step(api, self.train, self.mesh)(shape)
        rows = ranks * mix["seqs_per_rank"]
        bshape = {k: jax.ShapeDtypeStruct((rows, mix["seq_len"]), jnp.int32)
                  for k in ("tokens", "labels")}
        _, self.batch_sharding = step_lib.batch_specs(bshape, self.mesh, self.train)
        named = specs["named"]
        self.state_shape, self.state_sharding = shape, named
        self.step = jax.jit(step_fn, in_shardings=(named, self.batch_sharding),
                            out_shardings=(named, None), donate_argnums=(0,))
        self.draw = jax.jit(functools.partial(draw_params, params_shape=shape.params,
                                              family=fam),
                            out_shardings=named.params)
        start = t["optimizer"]["warmup_steps"]

        def assemble(params):
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shape)
            return dataclasses.replace(zeros, params=params,
                                       step=jnp.full(shape.step.shape, start, shape.step.dtype))

        self.assemble = jax.jit(assemble, out_shardings=named, donate_argnums=(0,))
        self.b1 = t["optimizer"]["b1"]
        self.param_count = sum(math.prod(x.shape) for x in jax.tree.leaves(shape.params))

    def initial_state(self, key):
        """The train state the set-up steps start from, and a host copy
        of its weights (for the check, which must add nothing to the
        device's memory)."""
        params = self.draw(key)
        host = jax.device_get(params)
        return self.assemble(params), host

    def put(self, batch):
        return jax.device_put(batch, self.batch_sharding)
