"""One run of one cell: set-up, the measured window, the check.

Set-up builds the program's step and its state from the seed, then
drives that same object through its first three steps on the window's
own feed. Those steps compile the step and are what the check reads:
the loss of each step, each leaf's first gradient as the optimizer got
it (its first moment after one step, over ``1 - b1``), and each leaf's
change after three steps, taken on the host from copies of the first
weights and of the weights after three steps, so that the check adds
nothing to the device's memory; the norms of the change are worked
out once the window has closed, off the set-up's clock. The window then steps on until ``seconds``
have passed, one step queued ahead of the one being read back. When it
closes, peak memory is read, the program's state is freed, and the
plain reference (``chipbench/reference``) makes the same three steps
from the same weights and batches.
"""

from __future__ import annotations

import gc
import math
import os
import shutil
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import program as program_lib
from chipbench.harness import spec as spec_lib
from chipbench.harness.traffic import LMTraffic
from chipbench.reference.train import ReferenceTrainer

SETUP_STEPS = 3
TRACE_DIR = os.path.join(spec_lib.ROOT, ".bench_trace")


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def _change_on_host(params, first):
    """Per-leaf norms of ``params - first``, both on the host: the check
    adds nothing to the device's memory."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(params))
    out = {}
    for (p, x), y in zip(flat, jax.tree.leaves(first)):
        d = np.asarray(x, np.float32) - np.asarray(y, np.float32)
        out[program_lib.leaf_path(p)] = float(np.sqrt(np.sum(np.square(d), dtype=np.float64)))
    return out


def memory(devices, what):
    """Logs the fullest device's bytes in use and peak so far."""
    stats = [d.memory_stats() or {} for d in devices]
    use = max(s.get("bytes_in_use", 0) for s in stats)
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"memory {what}: in use {use} B, peak {peak} B")
    return peak


def _named(tree, values):
    paths = [program_lib.leaf_path(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return dict(zip(paths, (float(v) for v in jax.device_get(values))))


def devices_for(chips, require_tpu=True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def program_readings(prog, mix, key, t_start=None):
    """Set-up steps through the timed path; returns (state, readings).
    The readings hold host copies of the first weights and of those
    after the set-up steps until ``with_change`` reads the change."""
    state, first = prog.initial_state(key)
    losses = []
    for k in range(SETUP_STEPS):
        state, met = prog.step(state, prog.put(mix.batch(k)))
        losses.append(float(met["loss"]))
        if t_start is not None:
            log(f"set-up step {k} done at {time.perf_counter() - t_start:.3f} s")
            memory(prog.mesh.devices.flat, f"after set-up step {k}")
        if k == 0:
            grads = _named(state.opt["m"], _norms(state.opt["m"]))
            grads = {p: v / (1.0 - prog.b1) for p, v in grads.items()}
    return state, {"loss": losses, "grad": grads, "first": first,
                   "after": jax.device_get(state.params)}


def with_change(readings):
    """``readings`` with the change in place of the two weight copies."""
    r = dict(readings)
    r["change"] = _change_on_host(r.pop("after"), r.pop("first"))
    return r


def reference(cell, devices, mode="f32"):
    cfg = cell["config"]
    return ReferenceTrainer(cfg, cell["traffic"], spec_lib.family(cfg), devices, mode=mode)


def reference_readings(ref, mix, params, fault=None):
    """The reference's readings from ``params``, on the batches of the
    set-up steps (``fault``: see ``ReferenceTrainer.step``)."""
    st = ref.init(params)
    losses = []
    for k in range(SETUP_STEPS):
        b = mix.batch(k)
        st, loss, g = ref.step(st, b, fault)
        losses.append(loss)
        if k == 0:
            grads = _named(g, _norms(g))
        del g
    return {"loss": losses, "grad": grads, "change": _change_on_host(st["params"], params)}


def _leaf_gap(prog, ref, keep):
    med = statistics.median(ref[p] for p in keep)
    return max((abs(prog[p] - ref[p]) / max(ref[p], med), p) for p in keep)


def detail(prog, ref):
    """Per-leaf relative gaps and per-step loss gaps (for calibration)."""
    med = statistics.median(ref["grad"].values())
    cmed = statistics.median(ref["change"].values())
    return {
        "loss": [a - b for a, b in zip(prog["loss"], ref["loss"])],
        "grad": {p: (prog["grad"][p] - v) / max(v, med) for p, v in ref["grad"].items()},
        "change": {p: (prog["change"][p] - v) / max(v, cmed) for p, v in ref["change"].items()},
        "ref_grad": ref["grad"], "ref_change": ref["change"],
    }


def compare(prog, ref):
    """The numbers the check compares (see PERF.md, "How correct is
    decided"): the worst step's loss gap, and by the worst leaf the gap
    between the program's and the reference's norms of the first
    gradient and of the change over three steps, against the larger of
    that leaf's reference norm and the median leaf's. Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are left out of the change."""
    med = statistics.median(ref["grad"].values())
    moving = [p for p, v in ref["grad"].items() if v >= 1e-3 * med]
    grad, grad_leaf = _leaf_gap(prog["grad"], ref["grad"], list(ref["grad"]))
    change, change_leaf = _leaf_gap(prog["change"], ref["change"], moving)
    log(f"worst leaf: gradient {grad_leaf}, change {change_leaf}")
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"])),
        "grad_norm_gap": grad,
        "change_norm_gap": change,
    }


def window(prog, mix, state, seconds, first_step):
    """Steps until ``seconds`` have passed; returns (state, steps,
    elapsed, losses). Time runs from the first dispatch to the
    ``block_until_ready`` of the last step."""
    from jax.profiler import TraceAnnotation
    k = first_step
    with TraceAnnotation("bench.batch"):
        host = mix.batch(k)
    with TraceAnnotation("bench.device_put"):
        batch = prog.put(host)
    pending, losses, steps = [], [], 0
    t0 = time.perf_counter()
    with TraceAnnotation("bench.window"):
        while True:
            with TraceAnnotation("bench.dispatch"):
                state, met = prog.step(state, batch)
            pending.append(met["loss"])
            steps += 1
            k += 1
            if time.perf_counter() - t0 < seconds:
                with TraceAnnotation("bench.batch"):
                    host = mix.batch(k)
                with TraceAnnotation("bench.device_put"):
                    batch = prog.put(host)
            if len(pending) > 1:
                with TraceAnnotation("bench.readback"):
                    losses.append(float(pending.pop(0)))
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.readback"):
            jax.block_until_ready(state)
            losses.extend(float(x) for x in pending)
    return state, steps, time.perf_counter() - t0, losses


def run(name, seed, seconds, trace, t_start, require_tpu=True, tamper=None,
        cell=None):
    """Returns (result dict, compared dict). ``tamper``: a function that
    takes the built program and breaks its timed path; ``cell``: the
    cell's files, given in place of looking them up (tests only)."""
    cell = cell or spec_lib.cell(name)
    devices = devices_for(cell["chips"], require_tpu)
    cfg, fam = cell["config"], spec_lib.family(cell["config"])
    log(f"devices ready at {time.perf_counter() - t_start:.3f} s")
    prog = program_lib.Program(cell, devices)
    if tamper is not None:
        tamper(prog)
    mix = LMTraffic(cell["traffic"], cfg["model"]["vocab_size"], seed)
    key = program_lib.seed_key(seed)
    log(f"program built at {time.perf_counter() - t_start:.3f} s")
    state, readings = program_readings(prog, mix, key, t_start)
    setup_s = time.perf_counter() - t_start
    memory(devices, "after set-up")
    log(f"set-up {setup_s:.3f} s, losses {readings['loss']}")

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    state, steps, elapsed, losses = window(prog, mix, state, seconds, SETUP_STEPS)
    if trace:
        jax.profiler.stop_trace()
    failed = sum(1 for x in losses if not math.isfinite(x))
    peak = memory(devices, "after the window")
    log(f"window {steps} steps in {elapsed:.6f} s")
    del state
    gc.collect()

    red = None
    if trace:
        from chipbench.harness import trace as trace_lib
        red = trace_lib.reduce_dir(TRACE_DIR, spec_lib.names(), len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    first = readings["first"]
    readings = with_change(readings)
    t_ref = time.perf_counter()
    trainer = reference(cell, devices)
    ref = reference_readings(trainer, mix, first)
    log(f"reference {time.perf_counter() - t_ref:.3f} s, losses {ref['loss']}")
    log("reference by phase: " + ", ".join(f"{k} {v:.3f} s" for k, v in trainer.seconds.items()))
    memory(devices, "after the reference")
    numbers = compare(readings, ref)
    for k, v in numbers.items():
        if k not in cell["limits"]:
            log(f"read, not compared (no limit; see PERF.md): {k} {v!r}")
    compared = {k: {"value": v, "limit": cell["limits"][k]["limit"]}
                for k, v in numbers.items() if k in cell["limits"]}
    correct = failed == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in compared.values())

    tokens = steps * mix.tokens_per_step
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if not trace:
        result["metrics"] = {
            "tokens_per_s": {"value": tokens / elapsed, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = device
        return result, compared

    ctx = {"trace": red, "steps": steps, "chips": len(devices),
           "param_count": prog.param_count, "config": cfg,
           "peaks": spec_lib.peaks(d0.device_kind), "peak_bytes": peak,
           "flops_per_step": fam.flops_per_token(cfg["model"], mix.seq_len) * mix.tokens_per_step}
    metrics = {}
    for m in cell["per_layer"]:
        value = spec_lib.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    result.update(metrics=metrics, device=device, breakdown=red["breakdown"])
    return result, compared
