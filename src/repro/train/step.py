"""Train-step builder: manual-DP ``shard_map`` around auto-TP GSPMD.

The step is organised exactly like the paper's Algorithm 1 deployment:

  1. each (pod, data) worker computes *local* gradients (auto TP inside);
  2. gradients are aggregated across the DP axes by a pluggable
     :class:`~repro.core.aggregators.Aggregator` strategy selected by
     ``tc.aggregator`` — ``"dense"`` (plain ``psum``, the NCCL-baseline
     arm), ``"compressed"`` (the paper's pipeline over fixed-size
     gradient buckets: ONE sketch encode + ONE stacked sketch-``psum`` +
     ONE index OR-AllReduce for the whole pytree, optionally pipelined
     per wire chunk through the shared stream scheduler —
     ``cfg.overlap`` / ``cfg.stream_chunks``, ``core/streams.py``),
     ``"compressed_rs"`` (the reduce-scatter wire: ``psum_scatter``
     sketch + OR-Reduce-Scatter bitmap where supported, so each DP rank
     receives and peels only its own 1/W bucket range — the natural
     partner of the ZeRO-1 sharded optimizer, including the PR 5
     gather-skip path: when the stream chunk grid aligns with the
     ZeRO-1 slices, per-rank recovered chunks feed the optimizer
     shards directly and the recovered-chunk all_gather disappears
     (``tc.rs_gather_skip``)), or
     ``"compressed_innet"`` (the emulated in-network tier of PR 4: the
     stream rides a worker->ToR->spine switch tree from ``repro.net``
     once per worker — integer-add sketch over the fixed-point wire
     when ``compression.wire_dtype='fxp32'``, OR bitmap — so the
     hottest link carries 1x the payload vs the ring's 2(W-1)/W x), or
     ``"auto"`` (PR 6: per-bucket-group wire selection — the step
     executes a ``WirePlan`` from the host-side cost-model controller,
     passed via ``build_train_step(..., wire_plan=...)``, and surfaces
     per-bucket occupancy telemetry back through the metrics);
  3. the optimizer applies the aggregated gradient — replicated, or
     ZeRO-1-sharded across the DP axes (slice-update-allgather).

Error-feedback residuals keep the parameter pytree layout (sparsification
is per leaf — see ``core/aggregators``); the bucketed strategies expose
per-bucket residual views through ``BucketPlan.residual_slices``.

Everything lives in one jittable function so the multi-pod dry-run can
``lower().compile()`` it with placeholder inputs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from repro import compat
from repro.core import aggregators as agg_lib
from repro.core import collectives as coll
from repro.core import streams as streams_lib
from repro.models.registry import ModelAPI
from repro.parallel import sharding as shd
from repro.parallel.hints import logical_axis_rules
from .config import TrainConfig
from . import optimizer as opt_lib


# ----------------------------------------------------------------------
# Train state (a pytree)
# ----------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: Any
    residual: Any          # EF residuals, leading dp axis (or (0,) stubs)
    step: jnp.ndarray


def effective_dp_axes(prof, mesh) -> tuple:
    """dp axes restricted to those the mesh actually has."""
    return tuple(a for a in prof.dp_axes if a in mesh.shape)


def _dp_total(mesh, dp_axes) -> int:
    n = 1
    for a in dp_axes:
        n *= mesh.shape[a]
    return n


def init_train_state(api: ModelAPI, tc: TrainConfig, mesh, key) -> TrainState:
    params = api.init(key)
    opt = opt_lib.init_opt_state(params, tc.optimizer)
    dp = _dp_total(mesh, effective_dp_axes(tc.sharding, mesh))
    ccfg = tc.compression
    if tc.aggregator != "dense" and ccfg.topk_ratio is not None \
            and ccfg.error_feedback:
        residual = jax.tree.map(
            lambda p: jnp.zeros((dp,) + p.shape, jnp.float32), params)
    else:
        residual = jax.tree.map(lambda p: jnp.zeros((0,), jnp.float32), params)
    return TrainState(params=params, opt=opt, residual=residual,
                      step=jnp.zeros((), jnp.int32))


# ----------------------------------------------------------------------
# Sharding trees for the state / batch
# ----------------------------------------------------------------------

# The ZeRO-1 slice-dim rule lives in core/streams.py: the reduce-scatter
# aggregator's gather-skip predicate checks alignment against the exact
# same definition, so the slice the optimizer consumes and the slice the
# aggregator validates can never drift apart.
def _zero_slice_dim(shape, spec: P, dp: int,
                    stacked_dim0: bool = False) -> Optional[int]:
    del stacked_dim0
    return streams_lib.zero_slice_dim(shape, spec, dp)


def state_specs(state: TrainState, tc: TrainConfig, mesh) -> Dict[str, Any]:
    """Returns dict with 'full' (NamedShardings for jit in/out) and
    'manual' (PartitionSpecs over the manual dp axes for shard_map)."""
    prof = tc.sharding
    dp_axes = effective_dp_axes(prof, mesh)
    dp = _dp_total(mesh, dp_axes)
    pspecs = shd.param_pspecs(state.params, prof)

    # params: auto axes only (manual spec is replicated P())
    p_manual = jax.tree.map(lambda s: P(), pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    # optimizer: ZeRO-1 slices on the dp axes where possible
    def opt_specs(param_spec: P, leaf):
        if not prof.zero1 or dp == 1:
            return P(), param_spec
        d = _zero_slice_dim(leaf.shape, param_spec, dp, False)
        if d is None:
            return P(), param_spec
        parts_m = [None] * leaf.ndim
        parts_m[d] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
        manual = P(*parts_m)
        parts_f = list(param_spec) + [None] * (leaf.ndim - len(param_spec))
        parts_f[d] = parts_m[d]
        return manual, P(*parts_f)

    opt_manual, opt_full = {}, {}
    for mom, tree in state.opt.items():
        opt_manual[mom] = jax.tree.map(
            lambda leaf, s: opt_specs(s, leaf)[0], tree, pspecs)
        opt_full[mom] = jax.tree.map(
            lambda leaf, s: opt_specs(s, leaf)[1], tree, pspecs)

    # EF residual: leading dp axis + the param's own tp sharding shifted
    def res_manual(r):
        if r.ndim == 1 and r.shape[0] == 0:
            return P()
        return P(dp_axes if len(dp_axes) > 1 else dp_axes[0])

    def res_full(r, s):
        if r.ndim == 1 and r.shape[0] == 0:
            return P()
        return P(*((dp_axes if len(dp_axes) > 1 else dp_axes[0],) + tuple(s)))

    r_manual = jax.tree.map(res_manual, state.residual)
    r_full = jax.tree.map(res_full, state.residual, pspecs)

    manual = TrainState(params=p_manual, opt=opt_manual, residual=r_manual,
                        step=P())
    full = TrainState(params=pspecs, opt=opt_full, residual=r_full, step=P())
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), full,
                         is_leaf=lambda x: isinstance(x, P))
    return {"manual": manual, "full": full, "named": named,
            "pspecs": pspecs}


def batch_specs(batch_shapes: Dict[str, Any], mesh, tc: TrainConfig):
    """Manual + named shardings for a training batch (dict of arrays).

    The manual spec covers only the DP (shard_map) axes; the named
    sharding additionally spreads the batch over any *auto* batch axes
    (ShardingProfile.batch_auto_axes, e.g. kimi's "data"=EP axis)."""
    prof = tc.sharding
    dp_axes = effective_dp_axes(prof, mesh)
    ax = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    auto = tuple(a for a in prof.batch_auto_axes if a in mesh.shape)
    full_axes = tuple(dp_axes) + auto
    fax = full_axes if len(full_axes) > 1 else (
        full_axes[0] if full_axes else None)

    manual = jax.tree.map(lambda _: P(ax) if ax else P(), batch_shapes)
    named = jax.tree.map(
        lambda _: NamedSharding(mesh, P(fax) if fax else P()), batch_shapes)
    return manual, named


# ----------------------------------------------------------------------
# The step itself
# ----------------------------------------------------------------------

def build_train_step(api: ModelAPI, tc: TrainConfig, mesh, *,
                     wire_plan=None):
    """Returns (step_fn, specs) where step_fn(state, batch) -> (state,
    metrics) is ready for jax.jit with the provided shardings.

    ``wire_plan`` (PR 6): an explicit
    :class:`~repro.core.wireplan.WirePlan` applied to the aggregator —
    how the ``auto`` strategy's host-side controller
    (:class:`~repro.core.costmodel.AutoWireController`) swaps plans in:
    rebuild the step with the new plan every ``replan_every`` boundary
    (each plan is its own compiled step). Ignored when the effective
    strategy is dense (single DP rank, or ``tc.aggregator='dense'``).
    With ``tc.aggregator='auto'`` and no plan, the step executes the
    controller's analytic plan. The ``auto`` aggregator also surfaces
    its per-bucket occupancy telemetry as the (vector-valued)
    ``bucket_occupancy`` metric for the controller to fold back in.
    """
    prof = tc.sharding
    # drop dp axes the mesh doesn't have (e.g. "pod" on a single pod)
    dp_axes = effective_dp_axes(prof, mesh)
    dp = _dp_total(mesh, dp_axes)
    ocfg = tc.optimizer
    inside_rules = shd.filter_rules_for_mesh(
        prof.logical_rules(inside_manual_dp=True), mesh)
    # with no manual axes the step runs under plain jit: constraints must
    # carry the mesh (NamedSharding), not bare PartitionSpecs
    rules_mesh = None if dp_axes else mesh

    def _pin_one(x, spec):
        if rules_mesh is not None:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(rules_mesh, spec))
        return jax.lax.with_sharding_constraint(x, spec)

    def local_grads(params, batch, pspecs):
        """Per-worker gradients, with optional microbatch accumulation."""
        def loss_fn(p, b):
            with logical_axis_rules(inside_rules, mesh=rules_mesh):
                # ep_exchange is bound below (after the manual-axes set is
                # known) and read here at trace time, inside the manual
                # region where its collectives are legal.
                if ep_exchange is None:
                    loss, metrics = api.loss(p, b, remat=tc.remat)
                else:
                    loss, metrics = api.loss(p, b, remat=tc.remat,
                                             ep_exchange=ep_exchange)
            return loss, metrics

        def pin(grads):
            # keep the gradient (and its accumulation carry) on the
            # parameters' TP sharding — without this GSPMD can replicate
            # the f32 accumulator (full-size per device)
            return jax.tree.map(_pin_one, grads, pspecs)

        if tc.accum_steps <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, pin(grads)

        def split(x):
            return x.reshape((tc.accum_steps, x.shape[0] // tc.accum_steps)
                             + x.shape[1:])
        micro = jax.tree.map(split, batch)

        def acc_body(carry, mb):
            loss_a, grads_a = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            grads_a = pin(jax.tree.map(jnp.add, grads_a, grads))
            return (loss_a + loss, grads_a), metrics

        g0 = pin(jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params))
        (loss_sum, grads), metrics = jax.lax.scan(
            acc_body, (jnp.float32(0.0), g0), micro)
        inv = 1.0 / tc.accum_steps
        grads = jax.tree.map(lambda g: g * inv, grads)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum * inv, metrics, grads

    # Strategy selected once per step build; called inside the manual-DP
    # region. Compression packs shard-locally even in pure-DP profiles:
    # vocab-sharded embedding grads would otherwise be all-gathered to
    # full size before encoding (16+ GiB/step on a 3B model).
    step_manual = set(dp_axes)
    aggregator = agg_lib.make_aggregator(
        tc.aggregator if dp > 1 else "dense", tc.compression, mesh,
        dp_axes=dp_axes, tp_axes=((prof.tp_axis or "model"),),
        outer_manual=step_manual)
    if wire_plan is not None and not isinstance(aggregator,
                                                agg_lib.DenseAggregator):
        aggregator = dataclasses.replace(aggregator, wire_plan=wire_plan)
    # Full-manual step regions (the mesh has only DP axes) can gather
    # ZeRO-1 slices with a manual-axis all_gather — no auto axes left
    # for Shardy to un-shard, and half the wire of the zero-pad + psum
    # trick kept for partial-auto.
    manual_all_gather = bool(dp_axes) and \
        compat.full_manual_region(step_manual, mesh)

    # PR 8: the MoE expert-parallel combine wire. Built only when the
    # step can legally run it: MoE model, the profile's EP axes live on
    # this mesh, and every EP axis is manual in the step region (the
    # permute lanes need collective axis names — with no DP axes the
    # step runs under plain jit, so the model keeps the local combine).
    # The exchange codec runs at ratio 2.5 with EF/top-k off: expert
    # outputs are dense payloads, and at 2.5 the sketch capacity covers
    # the block even when every slot is occupied, so recovery — hence
    # the combine itself — is exact (no feedback residue to carry).
    ep_exchange = None
    ep_axes_eff = tuple(ax for ax in prof.ep_axes if ax in mesh.shape)
    if (tc.ep_exchange != "none" and getattr(api.cfg, "moe", None) is not None
            and dp_axes and ep_axes_eff
            and set(ep_axes_eff) <= set(step_manual)):
        ex_cfg = dataclasses.replace(tc.compression, ratio=2.5,
                                     topk_ratio=None, error_feedback=False)
        ep_exchange = agg_lib.make_exchange(
            tc.ep_exchange, ex_cfg, mesh, ep_axes_eff)

    def make_aggregate(agg):
        @jax.named_scope("aggregate")
        def aggregate(grads, residual, pspecs):
            if isinstance(agg, agg_lib.DenseAggregator):
                return coll.dense_all_reduce(grads, dp_axes), residual, None
            res_local = jax.tree.map(
                lambda r: r[0] if r.ndim > 1 else r, residual)
            out, new_state = agg(
                grads, coll.AggregationState(residual=res_local), pspecs)
            new_res = jax.tree.map(
                lambda old, r: r[None] if old.ndim > 1 else old,
                residual, new_state.residual)
            return out, new_res, new_state.telemetry
        return aggregate

    def _dp_rank():
        # Rank-major linearization shared with the collectives layer so
        # ZeRO-1 slice placement matches psum_scatter/all_gather tiling.
        return coll.linear_rank(dp_axes)

    @jax.named_scope("optimizer")
    def apply_updates(params, opt, grads, step, pspecs, norm_psum=False):
        lr = opt_lib.lr_schedule(step, ocfg)
        gnorm = opt_lib.global_grad_norm(grads)
        if norm_psum:
            # Gather-skip path: each rank holds a disjoint piece of the
            # aggregated gradient (exact inside its owned coordinates,
            # zero outside), so the global norm is the cross-rank psum
            # of the per-rank squared norms — every coordinate counted
            # exactly once.
            gnorm = jnp.sqrt(jax.lax.psum(gnorm * gnorm, tuple(dp_axes)))
        if ocfg.grad_clip:
            grads = opt_lib.clip_grads(grads, gnorm, ocfg.grad_clip)
        moms = list(opt.keys())

        def leaf_update(path_spec, p, g, *mom_leaves):
            st = {k: v for k, v in zip(moms, mom_leaves)}
            d = (_zero_slice_dim(p.shape, path_spec, dp, False)
                 if (prof.zero1 and dp > 1) else None)
            if d is None:
                new_p, new_st = opt_lib.opt_leaf_update(p, g, st, lr, step, ocfg)
                return new_p, tuple(new_st[k] for k in moms)
            blk = p.shape[d] // dp
            start = _dp_rank() * blk
            p_s = jax.lax.dynamic_slice_in_dim(p, start, blk, axis=d)
            g_s = jax.lax.dynamic_slice_in_dim(g, start, blk, axis=d)
            new_p_s, new_st = opt_lib.opt_leaf_update(p_s, g_s, st, lr, step,
                                                      ocfg)
            # Gather the updated slices. Full-manual regions use the
            # rank-major tiled all_gather (optimal AG ring); partial-auto
            # regions keep the scatter+psum trick instead: Shardy
            # un-shards the auto (TP) axes around a manual-axis
            # all_gather (full-size transient per device) while psum
            # keeps them sharded, at 2x the AG ring's wire. Both add the
            # exact per-rank delta once — bit-identical results.
            delta = (new_p_s - p_s).astype(p.dtype)
            if manual_all_gather:
                new_p = p + jax.lax.all_gather(delta, tuple(dp_axes),
                                               axis=d, tiled=True)
            else:
                full = jnp.zeros(p.shape, p.dtype)
                full = jax.lax.dynamic_update_slice_in_dim(full, delta,
                                                           start, axis=d)
                new_p = p + jax.lax.psum(full, dp_axes)
            return new_p, tuple(new_st[k] for k in moms)

        p_leaves, treedef = jax.tree.flatten(params)
        spec_leaves = treedef.flatten_up_to(pspecs)
        g_leaves = treedef.flatten_up_to(grads)
        mom_leaves = [treedef.flatten_up_to(opt[k]) for k in moms]
        new_p, new_mom = [], [[] for _ in moms]
        for i, (p, s, g) in enumerate(zip(p_leaves, spec_leaves, g_leaves)):
            np_, nst = leaf_update(s, p, g, *[m[i] for m in mom_leaves])
            new_p.append(np_)
            for j in range(len(moms)):
                new_mom[j].append(nst[j])
        params = jax.tree.unflatten(treedef, new_p)
        opt = {k: jax.tree.unflatten(treedef, new_mom[j])
               for j, k in enumerate(moms)}
        return params, opt, gnorm

    def make(state: TrainState):
        specs = state_specs(state, tc, mesh)
        pspecs = specs["pspecs"]

        # ZeRO-1 gather-skip (PR 5): hand the reduce-scatter aggregator
        # the per-leaf slice dims the optimizer will consume. When the
        # stream chunk grid aligns with them, the aggregator feeds each
        # rank's optimizer shard directly and skips the recovered-chunk
        # all_gather; the step then reduces the grad-norm across ranks
        # (the only consumer of off-shard gradient values).
        aggregator_use, norm_psum = aggregator, False
        if (prof.zero1 and tc.rs_gather_skip and dp > 1 and isinstance(
                aggregator, agg_lib.CompressedReduceScatterAggregator)):
            p_leaves, treedef = jax.tree.flatten(state.params)
            spec_leaves = treedef.flatten_up_to(pspecs)
            dims = tuple(_zero_slice_dim(p.shape, s, dp)
                         for p, s in zip(p_leaves, spec_leaves))
            aggregator_use = dataclasses.replace(aggregator,
                                                 zero1_dims=dims)
            norm_psum = aggregator_use.gather_skip_active(state.params,
                                                          pspecs)
        aggregate = make_aggregate(aggregator_use)

        def inner(params, opt, residual, step, batch):
            loss, metrics, grads = local_grads(params, batch, pspecs)
            grads, residual, telemetry = aggregate(grads, residual, pspecs)
            params, opt, gnorm = apply_updates(params, opt, grads, step,
                                               pspecs, norm_psum=norm_psum)
            # cross-worker metric reduction
            loss = jax.lax.psum(loss, dp_axes) / dp if dp_axes else loss
            metrics = {k: (jax.lax.psum(v, dp_axes) / dp if dp_axes else v)
                       for k, v in metrics.items()}
            metrics["grad_norm"] = gnorm
            metrics["loss"] = loss
            if telemetry is not None:
                # Per-bucket occupancy for the `auto` wire-plan
                # controller. Computed from the aggregated stream, so it
                # is already identical on every rank — no reduction.
                metrics["bucket_occupancy"] = telemetry["bucket_occupancy"]
            return params, opt, residual, metrics

        def step_fn(state: TrainState, batch):
            if dp_axes:
                bm, _ = batch_specs(batch, mesh, tc)
                sm = specs["manual"]
                fn = compat.shard_map(
                    inner, mesh=mesh,
                    in_specs=(sm.params, sm.opt, sm.residual, P(), bm),
                    out_specs=(sm.params, sm.opt, sm.residual, P()),
                    axis_names=step_manual,
                    check_vma=False)
            else:
                fn = inner          # no DP axes: pure auto-sharded step
            params, opt, residual, metrics = fn(
                state.params, state.opt, state.residual, state.step, batch)
            return TrainState(params=params, opt=opt, residual=residual,
                              step=state.step + 1), metrics

        return step_fn, specs

    return make
