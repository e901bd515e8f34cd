"""Training loop: step dispatch + checkpointing + failure recovery +
straggler accounting. This is the piece a cluster job actually runs.

Each step runs inside ``jax.profiler.StepTraceAnnotation("train")``
with host spans ``train.batch``, ``train.dispatch``, ``train.readback``
(the ``float(loss)`` that waits for the step) and ``train.checkpoint``,
so a ``jax.profiler`` trace of a job puts the host beside the device.

Control flow on failure (simulated or real):
  detect -> (optionally shrink world / rebuild mesh) -> restore last
  checkpoint with resharding -> replay the deterministic data stream from
  the restored step -> continue. ``run_training`` survives any number of
  injected failures up to ``RecoveryPolicy.max_restarts``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.ckpt import checkpoint as ckpt
from repro.data.pipeline import batch_fn
from repro.ft.failures import (FailureSimulator, InjectedFailure,
                               RecoveryPolicy, StragglerMonitor)
from repro.models.registry import ModelAPI
from .config import TrainConfig
from .step import (TrainState, init_train_state, build_train_step,
                   batch_specs, state_specs)


@dataclasses.dataclass
class TrainResult:
    losses: List[float]
    metrics: List[Dict[str, float]]
    restarts: int
    straggler_events: List[dict]
    final_step: int
    state: Any


def run_training(api: ModelAPI, tc: TrainConfig, mesh, *,
                 global_batch: int, seq_len: int, steps: int,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 failure_sim: Optional[FailureSimulator] = None,
                 recovery: RecoveryPolicy = RecoveryPolicy(),
                 log_every: int = 10,
                 log_fn: Callable[[str], None] = print) -> TrainResult:
    make_batch = batch_fn(api.cfg, global_batch, seq_len, seed=tc.seed)
    monitor = StragglerMonitor()
    saver = ckpt.AsyncCheckpointer()

    # The state is created directly in its shardings: each device
    # materialises only its own share of the parameters, optimizer state
    # and (dp, ...) error-feedback residual.
    init = functools.partial(init_train_state, api, tc, mesh)
    key = jax.random.PRNGKey(tc.seed)
    make = build_train_step(api, tc, mesh)
    step_fn, specs = make(jax.eval_shape(init, key))
    init = jax.jit(init, out_shardings=specs["named"])
    state = init(key)
    _, bnamed = batch_specs(make_batch(0), mesh, tc)
    jitted = jax.jit(step_fn, in_shardings=(specs["named"], bnamed),
                     out_shardings=(specs["named"], None),
                     donate_argnums=(0,))

    # resume if a checkpoint exists
    start = 0
    if ckpt_dir and (last := ckpt.latest_step(ckpt_dir)) is not None:
        state = ckpt.restore(ckpt_dir, last, template=state,
                             shardings=specs["named"])
        start = last
        log_fn(f"[loop] resumed from checkpoint step {start}")

    losses: List[float] = []
    all_metrics: List[Dict[str, float]] = []
    restarts = 0
    step = start
    while step < steps:
        try:
            with StepTraceAnnotation("train", step_num=step):
                t0 = time.perf_counter()
                if failure_sim is not None:
                    failure_sim.check(step)
                with TraceAnnotation("train.batch"):
                    batch = jax.tree.map(lambda a, s: jax.device_put(a, s),
                                         make_batch(step), bnamed)
                with TraceAnnotation("train.dispatch"):
                    state, metrics = jitted(state, batch)
                with TraceAnnotation("train.readback"):
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                monitor.observe(step, dt)
                losses.append(loss)
                # vector metrics (e.g. the `auto` strategy's per-bucket
                # occupancy telemetry) are kept as lists, scalars as floats
                all_metrics.append({
                    k: float(v) if np.ndim(v) == 0
                    else np.asarray(v).tolist()
                    for k, v in metrics.items()})
                if log_every and step % log_every == 0:
                    log_fn(f"[loop] step {step} loss {loss:.4f} "
                           f"({dt*1e3:.0f} ms)")
                step += 1
                if ckpt_dir and step % ckpt_every == 0:
                    with TraceAnnotation("train.checkpoint"):
                        saver.save(ckpt_dir, step, state,
                                   metadata={"loss": loss})
        except InjectedFailure as e:
            restarts += 1
            log_fn(f"[loop] FAILURE detected: {e}; restart {restarts}")
            if restarts > recovery.max_restarts:
                raise
            if ckpt_dir is None:
                raise
            saver.wait()
            last = ckpt.latest_step(ckpt_dir)
            if last is None:
                # no checkpoint yet: restart from scratch
                state = init(key)
                step = 0
            else:
                state = ckpt.restore(ckpt_dir, last, template=state,
                                     shardings=specs["named"])
                step = last
            log_fn(f"[loop] recovered at step {step}")

    saver.wait()
    return TrainResult(losses=losses, metrics=all_metrics, restarts=restarts,
                       straggler_events=monitor.events, final_step=step,
                       state=state)
