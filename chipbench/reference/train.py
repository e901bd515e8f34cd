"""The reference training step: what a configuration says one step computes.

Per data-parallel rank: the mean loss and float32 gradient over the
rank's microbatches. Across ranks: the mean gradient, either dense or,
on the compressed wire, through per-leaf top-k with error feedback (a
threshold at the ``1 - k/n`` quantile, linear interpolation, of every
``n // 4096``-th magnitude of gradient plus residual, ``k = int(n *
topk_ratio)``), the sum of the ranks' sketches, the OR of their bitmaps
and the peel of ``reference/codec.py``. Then clipping by the global
norm and AdamW with warm-up and cosine decay, parameters kept in the
dtype they are stored in. The state starts with zero moments at the
step that ends the warm-up, as the benchmark's program state does.

Ranks run side by side, one per device, under ``shard_map``. The codec
splits its blocks across the same devices. Nothing here imports the
program.

Memory: the parameters and both moments live on the host between
steps. The gradient pass takes only the parameters' float32 copy, made
on the devices, and returns the microbatch sums (the scan's accumulator
is the output buffer); the jitted call that consumes them divides by the
microbatch count. The update is jitted with the parameters, the moments
and the gradient donated, so it writes in place. A device then holds at
most the float32 parameters, the accumulator, one microbatch's gradient
and its activations during the gradient pass (12 bytes a parameter and
the activations), and the parameters, moments and gradient during the
update (14 bytes a parameter for bfloat16 parameters).
"""

from __future__ import annotations

import math
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.reference import codec
from chipbench.reference.precision import Dot

SAMPLES = 4096
CHUNK = 128          # sketch blocks a device peels at a time


def _topk_ef(g, res, ratio):
    full = g + res
    n = full.shape[0]
    k = max(1, int(n * ratio))
    if k >= n:
        return full, jnp.zeros_like(full)
    sample = jnp.abs(full[:: max(1, n // SAMPLES)])
    thresh = jnp.quantile(sample, 1.0 - k / n)
    sparse = jnp.where(jnp.abs(full) >= thresh, full, 0.0)
    return sparse, full - sparse


def lr_at(step, o):
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    frac = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (1 + math.cos(math.pi * prog))
    return o["lr"] * warm * frac


def _adamw(params, m, v, g, lr, t, o, m_store=1.0):
    """One AdamW update; ``m_store`` scales the first moment as it is
    stored (1 but for the planted ``altered`` fault)."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    if o["grad_clip"]:
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9)), g)
    m = jax.tree.map(lambda a, x: o["b1"] * a + (1 - o["b1"]) * x, m, g)
    v = jax.tree.map(lambda a, x: o["b2"] * a + (1 - o["b2"]) * x * x, v, g)

    def upd(p, a, b):
        pf = p.astype(jnp.float32)
        u = (a / (1 - o["b1"] ** t)) / (jnp.sqrt(b / (1 - o["b2"] ** t)) + o["eps"])
        return (pf - lr * (u + o["weight_decay"] * pf)).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), jax.tree.map(lambda a: a * m_store, m), v, g


class _Clock:
    """Seconds by phase: each call waits for ``out`` and books the
    time since the previous call to ``phase``."""

    def __init__(self, seconds):
        self.seconds, self.t = seconds, time.perf_counter()

    def __call__(self, phase, out):
        jax.block_until_ready(out)
        now = time.perf_counter()
        self.seconds[phase] += now - self.t
        self.t = now
        return out


class ReferenceTrainer:
    """Steps the reference. ``mode``: ``"f32"``, or ``"fp8"`` for the
    control. ``seconds`` sums the wall time of each phase of the steps
    taken (host transfers included, first calls compile)."""

    def __init__(self, cfg, traffic, family, devices, mode="f32"):
        self.seconds = defaultdict(float)
        self.m, self.t = cfg["model"], cfg["train"]
        self.ranks = traffic["ranks"]
        self.accum = self.t["accum_steps"]
        self.compressed = self.t["aggregator"] != "dense" and self.ranks > 1
        self.mesh = Mesh(np.array(devices[: self.ranks]), ("r",))
        self.geo = codec.Geometry(self.t["compression"]) if self.compressed else None
        dot = Dot(mode)
        m = self.m

        def micro_loss(p32, toks, labs):
            f = lambda a, b: family.sequence_loss(p32, a, b, m, dot)
            return jnp.mean(jax.vmap(f)(toks, labs))

        n_micro = self.accum

        def rank_grads(p32, toks, labs):
            mb = lambda x: x.reshape((self.accum, -1) + x.shape[1:])[:n_micro]

            def body(acc, xs):
                loss, g = jax.value_and_grad(micro_loss)(p32, *xs)
                return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

            zero = jax.tree.map(jnp.zeros_like, p32)
            (loss, g), _ = jax.lax.scan(body, (jnp.float32(0), zero), (mb(toks), mb(labs)))
            return loss, g      # sums: the consumer divides by n_micro

        ratio = self.t["compression"]["topk_ratio"]
        ef = self.t["compression"]["error_feedback"]

        def grads(p32, toks, labs):
            loss, g = rank_grads(p32, toks, labs)
            return loss[None], jax.tree.map(lambda a: a[None], g)

        def pack(g, res):
            leaves, tdef = jax.tree.flatten(g)
            rl = tdef.flatten_up_to(res)
            out = [_topk_ef((a[0] / n_micro).reshape(-1), r[0].reshape(-1) if ef else 0.0, ratio)
                   for a, r in zip(leaves, rl)]
            stream = jnp.concatenate([s for s, _ in out])
            new_res = [(nr if ef else jnp.zeros_like(nr)).reshape(r.shape)
                       for (_, nr), r in zip(out, rl)]
            return stream[None], jax.tree.unflatten(tdef, new_res)

        rep, rk = P(), P("r")
        self._grads = jax.jit(jax.shard_map(
            grads, mesh=self.mesh, in_specs=(rep, rk, rk), out_specs=(rk, rk),
            check_vma=False))
        self._pack = jax.jit(jax.shard_map(
            pack, mesh=self.mesh, in_specs=(rk, rk), out_specs=(rk, rk),
            check_vma=False), donate_argnums=(0, 1))
        self._to_f32 = jax.jit(lambda p: jax.tree.map(lambda a: a.astype(jnp.float32), p))
        self._dense_mean = jax.jit(lambda g: jax.tree.map(lambda a: (a / n_micro).mean(0), g))
        self._adamw = jax.jit(_adamw, static_argnums=(6,), donate_argnums=(0, 1, 2, 3))
        self._decode = jax.jit(self._aggregate_stream)

    def _aggregate_stream(self, streams, keep):
        """(R, n) sparse streams -> the mean gradient stream (n,), over the
        ranks ``keep`` (R,) marks. Each device peels its share of the
        blocks, ``CHUNK`` blocks at a time."""
        geo, R = self.geo, self.ranks
        n = streams.shape[1]
        nb = -(-n // geo.block)
        nb += (-nb) % (R * CHUNK)
        streams = streams * keep[:, None]
        x = jnp.pad(streams, ((0, 0), (0, nb * geo.block - n)))
        x = x.reshape(x.shape[0], nb, geo.group, geo.lanes)
        spec = NamedSharding(self.mesh, P("r"))
        total = jax.lax.with_sharding_constraint(x.sum(0), spec)
        bits = jax.lax.with_sharding_constraint(jnp.any(x != 0, axis=0), spec)
        ids = jax.lax.with_sharding_constraint(jnp.arange(nb, dtype=jnp.int32), spec)

        def local(t, b, i):
            shape = (-1, CHUNK) + t.shape[1:]

            def one(args):
                t, b, i = args
                return codec.decode(codec.encode(t, i, geo), b, i, geo)[0]

            v = jax.lax.map(one, (t.reshape(shape), b.reshape(shape), i.reshape(-1, CHUNK)))
            return v.reshape(t.shape)

        vals = jax.shard_map(local, mesh=self.mesh, in_specs=(P("r"),) * 3,
                             out_specs=P("r"), check_vma=False)(total, bits, ids)
        return vals.reshape(-1)[:n] / R

    def init(self, params):
        """The state from ``params``: parameters and zero moments on the
        host, error-feedback residuals on the devices."""
        params = jax.device_get(params)
        R = self.ranks
        res = None
        if self.compressed:     # each rank's residuals on its own device
            res = jax.jit(lambda: jax.tree.map(lambda p: jnp.zeros((R,) + p.shape, jnp.float32),
                                               params),
                          out_shardings=NamedSharding(self.mesh, P("r")))()
        zeros = lambda p: np.zeros(p.shape, np.float32)
        return {"params": params, "m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params), "res": res,
                "step": self.t["optimizer"]["warmup_steps"]}

    def _moment_sharding(self, p):
        """Moments split across the devices where they divide."""
        split = p.ndim and p.shape[0] % self.ranks == 0
        return NamedSharding(self.mesh, P("r") if split else P())

    def step(self, st, batch, fault=None):
        """One step; returns (new state, loss, clipped mean gradient).
        ``fault`` plants one in the reference put in the program's place:
        ``"half_batch"`` (each rank's second half of microbatches left
        out, the mean taken over the rest), ``"no_exchange"`` (the
        exchange between chips left out: rank 0 decodes its own sketch) or
        ``"altered"`` (the optimizer's first moment stored 1.5 times too
        large after each update)."""
        if fault == "half_batch":
            def half(x):
                x = x.reshape((self.ranks, self.accum, -1) + x.shape[1:])[:, : self.accum // 2]
                return np.concatenate([x, x], axis=1).reshape((-1,) + x.shape[3:])
            batch = {k: half(v) for k, v in batch.items()}
        keep = np.zeros(self.ranks, np.float32)
        keep[: 1 if fault == "no_exchange" else self.ranks] = 1.0
        clock = _Clock(self.seconds)
        replicated = NamedSharding(self.mesh, P())
        loss, g = clock("gradients", self._grads(
            self._to_f32(jax.device_put(st["params"], replicated)),
            batch["tokens"], batch["labels"]))
        res = st["res"]
        if self.compressed:
            stream, res = clock("pack", self._pack(g, res))
            del g
            leaves, tdef = jax.tree.flatten(st["params"])
            flat = clock("decode", self._decode(stream, keep))
            del stream
            offs = np.cumsum([0] + [p.size for p in leaves])
            g = jax.tree.unflatten(tdef, [flat[a:b].reshape(p.shape) for a, b, p
                                          in zip(offs[:-1], offs[1:], leaves)])
            del flat
        else:
            g = self._dense_mean(g)
        o = _Opt(self.t["optimizer"])
        put = lambda tree: jax.device_put(
            tree, jax.tree.map(self._moment_sharding, st["params"]))
        params, m, v, gc = self._adamw(jax.device_put(st["params"], replicated),
                                       put(st["m"]), put(st["v"]), g,
                                       lr_at(st["step"], o), st["step"] + 1.0, o,
                                       1.5 if fault == "altered" else 1.0)
        params, m, v = clock("update", jax.device_get((params, m, v)))
        new = {"params": params, "m": m, "v": v, "res": res, "step": st["step"] + 1}
        return new, float(jnp.mean(loss / self.accum)), gc


class _Opt(dict):
    """Hashable optimizer settings (a static argument of the jitted update)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
