"""8-device driver: full train_step (manual DP + auto TP) with dense and
compressed aggregation, ZeRO-1 on and off. Asserts loss decreases and the
two aggregators track each other. Also drives the PR 8 expert-parallel
all-to-all exchange (`TrainConfig.ep_exchange`) through the MoE combine
at W=2 and asserts both exchange wires train bit-identically to the
local combine."""
import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count=8 "
    "--xla_disable_hlo_passes=all-reduce-promotion")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import make_mesh
from repro.models import ModelConfig, MoEConfig, model_api
from repro.core import CompressionConfig
from repro.train import TrainConfig, OptimizerConfig, init_train_state, build_train_step
from repro.train.step import state_specs, batch_specs
from repro.parallel.sharding import ShardingProfile

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = ModelConfig(name="tiny", family="moe", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  moe=MoEConfig(num_experts=8, top_k=2, shared_experts=1,
                                expert_d_ff=64, capacity_factor=2.0),
                  dtype="float32")
api = model_api(cfg)
rng = np.random.default_rng(0)
B, S = 8, 32
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S))),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))}


def run(tc, steps=6):
    state = init_train_state(api, tc, mesh, jax.random.PRNGKey(0))
    make = build_train_step(api, tc, mesh)
    step_fn, specs = make(state)
    _, bnamed = batch_specs(batch, mesh, tc)
    jitted = jax.jit(step_fn,
                     in_shardings=(specs["named"], bnamed),
                     out_shardings=(specs["named"], None))
    b = jax.device_put(batch, bnamed)
    st = jax.device_put(state, specs["named"])
    losses = []
    for i in range(steps):
        st, m = jitted(st, b)
        losses.append(float(m["loss"]))
    return losses


opt = OptimizerConfig(lr=1e-2, warmup_steps=0, total_steps=100)
tc_dense = TrainConfig(aggregator="dense", optimizer=opt,
                       sharding=ShardingProfile(zero1=False), remat="block")
tc_dense_z = TrainConfig(aggregator="dense", optimizer=opt,
                         sharding=ShardingProfile(zero1=True), remat="block")
# sketch big enough for a fully dense gradient (paper Fig.3's ">= gamma*n"
# regime): recovery is lossless, so training must match dense psum.
tc_comp_ll = TrainConfig(aggregator="compressed", optimizer=opt,
                         compression=CompressionConfig(ratio=2.0, lanes=512,
                                                       rows=60, chunk_blocks=64),
                         sharding=ShardingProfile(zero1=True), remat="block")
# production setting: top-k budget + error feedback (dense-grad models)
tc_comp_tk = TrainConfig(aggregator="compressed", optimizer=opt,
                         compression=CompressionConfig(ratio=0.4, lanes=512,
                                                       rows=6, chunk_blocks=64,
                                                       topk_ratio=0.1),
                         sharding=ShardingProfile(zero1=True), remat="block")

l_dense = run(tc_dense)
print("dense        :", [round(x, 4) for x in l_dense])
l_dz = run(tc_dense_z)
print("dense+z1     :", [round(x, 4) for x in l_dz])
# strict losslessness check under a *linear* optimizer (momentum), where
# fp-eps recovery noise stays fp-eps instead of being amplified by Adam's
# rsqrt(v) at near-zero second moments.
opt_m = OptimizerConfig(kind="momentum", lr=1e-2, warmup_steps=0,
                        total_steps=100, grad_clip=0.0)
l_dense_m = run(TrainConfig(aggregator="dense", optimizer=opt_m,
                            sharding=ShardingProfile(zero1=False),
                            remat="block"))
l_ll_m = run(TrainConfig(aggregator="compressed", optimizer=opt_m,
                         compression=tc_comp_ll.compression,
                         sharding=ShardingProfile(zero1=False),
                         remat="block"))
print("dense (mom)  :", [round(x, 5) for x in l_dense_m])
print("comp  (mom)  :", [round(x, 5) for x in l_ll_m])
l_ll = run(tc_comp_ll)
print("comp lossless:", [round(x, 4) for x in l_ll])
# reduce-scatter aggregator: each DP rank peels only its bucket range,
# feeding the ZeRO-1 slice-update path; must track the lossless run.
l_rs = run(TrainConfig(aggregator="compressed_rs", optimizer=opt,
                       compression=tc_comp_ll.compression,
                       sharding=ShardingProfile(zero1=True), remat="block"))
print("comp rs+z1   :", [round(x, 4) for x in l_rs])
l_tk = run(tc_comp_tk)
print("comp topk+EF :", [round(x, 4) for x in l_tk])
# in-network tier (PR 4): f32 wire reuses the AllReduce collectives and
# must match the lossless compressed run exactly; the fxp32 switch wire
# adds only the documented ~2^-29-relative quantization, so the curve
# must stay on track.
import dataclasses
l_in = run(TrainConfig(aggregator="compressed_innet", optimizer=opt,
                       compression=tc_comp_ll.compression,
                       sharding=ShardingProfile(zero1=True), remat="block"))
print("comp innet   :", [round(x, 4) for x in l_in])
l_in_fx = run(TrainConfig(
    aggregator="compressed_innet", optimizer=opt,
    compression=dataclasses.replace(tc_comp_ll.compression,
                                    wire_dtype="fxp32"),
    sharding=ShardingProfile(zero1=True), remat="block"))
print("comp innet fx:", [round(x, 4) for x in l_in_fx])

assert l_dense[-1] < l_dense[0], "dense loss must decrease"
assert all(abs(a - b) < 1e-4 for a, b in zip(l_dense, l_dz)), \
    f"zero1 diverged from replicated: {l_dense} vs {l_dz}"
assert all(abs(a - b) < 1e-4 for a, b in zip(l_dense_m, l_ll_m)), \
    f"lossless compressed diverged under momentum: {l_dense_m} vs {l_ll_m}"
assert all(abs(a - b) < 0.1 for a, b in zip(l_dense, l_ll)), \
    f"lossless compressed (adam) off-track: {l_dense} vs {l_ll}"
assert all(abs(a - b) < 1e-4 for a, b in zip(l_ll, l_rs)), \
    f"reduce-scatter aggregator diverged from lossless: {l_ll} vs {l_rs}"
assert l_tk[-1] < l_tk[0] and l_tk[-1] < 5.0, \
    f"topk+EF compressed failed to converge: {l_tk}"
assert all(abs(a - b) < 1e-4 for a, b in zip(l_ll, l_in)), \
    f"in-network f32 wire diverged from lossless: {l_ll} vs {l_in}"
assert all(abs(a - b) < 0.05 for a, b in zip(l_ll, l_in_fx)), \
    f"in-network fxp32 wire off-track: {l_ll} vs {l_in_fx}"
assert l_in_fx[-1] < l_in_fx[0], "fxp32 training loss must decrease"

# PR 6: the `auto` strategy inside the real train step. Its analytic
# plan (no telemetry yet at trace time) must stay on the lossless track,
# and the per-bucket occupancy telemetry must surface through the step
# metrics as a vector for the host-side controller to fold back in.
def run_auto(tc, steps=6):
    state = init_train_state(api, tc, mesh, jax.random.PRNGKey(0))
    step_fn, specs = build_train_step(api, tc, mesh)(state)
    _, bnamed = batch_specs(batch, mesh, tc)
    jitted = jax.jit(step_fn, in_shardings=(specs["named"], bnamed),
                     out_shardings=(specs["named"], None))
    st = jax.device_put(state, specs["named"])
    b = jax.device_put(batch, bnamed)
    losses, occ = [], None
    for _ in range(steps):
        st, m = jitted(st, b)
        losses.append(float(m["loss"]))
        occ = np.asarray(m["bucket_occupancy"])
    return losses, occ


l_auto, occ = run_auto(TrainConfig(
    aggregator="auto", optimizer=opt,
    compression=tc_comp_ll.compression,
    sharding=ShardingProfile(zero1=True), remat="block"))
print("comp auto    :", [round(x, 4) for x in l_auto],
      f"occ=[{occ.min():.3f},{occ.max():.3f}] n_buckets={occ.size}")
assert all(abs(a - b) < 1e-4 for a, b in zip(l_ll, l_auto)), \
    f"auto strategy diverged from lossless: {l_ll} vs {l_auto}"
assert occ.ndim == 1 and occ.size >= 1, occ.shape
assert float(occ.min()) >= 0.0 and float(occ.max()) <= 1.0, occ

# PR 5: the streamed native RS wire (per-chunk psum_scatter staged
# against the next chunk's encode by core/streams.py) inside the real
# train step must stay exactly on the one-shot track.
l_rs_ov = run(TrainConfig(
    aggregator="compressed_rs", optimizer=opt,
    compression=dataclasses.replace(tc_comp_ll.compression, overlap=True),
    sharding=ShardingProfile(zero1=True), remat="block"))
print("comp rs ovl  :", [round(x, 4) for x in l_rs_ov])
assert all(abs(a - b) < 1e-4 for a, b in zip(l_rs, l_rs_ov)), \
    f"streamed RS wire diverged from one-shot in the step: {l_rs} vs {l_rs_ov}"

# PR 5 gather-skip inside the real train step: a stub model whose two
# 4-bucket leaves align with the ZeRO-1 slices on a 2-chunk grid. With
# tc.rs_gather_skip the step must drop the recovered-chunk all_gather
# (fewer all_gather eqns in the jaxpr) and train identically (the only
# off-shard consumer, the grad-norm, is psum-reduced on that path). The
# stub runs on a DP-only mesh, whose step region is full-manual, so the
# recovered-chunk gather is a manual-axis all_gather (a partial-auto
# region gathers with zero-pad + psum instead).
stub_mesh = make_mesh((2, 2), ("pod", "data"), devices=jax.devices()[:4])
from repro.models.registry import ModelAPI

E_skip = 1536  # bucket_elems of the skip compression config below
n_p = 4 * E_skip


def _stub_init(key):
    del key
    base = jnp.linspace(-1.0, 1.0, n_p, dtype=jnp.float32)
    return {"wa": base, "wb": base[::-1] * 0.5}


def _stub_loss(p, b, remat="none"):
    del remat
    pred = b["x"] * (p["wa"] + p["wb"])[None, :]
    loss = jnp.mean((pred - b["y"]) ** 2)
    return loss, {"mse": loss}


stub_api = ModelAPI(cfg=None, init=_stub_init, loss=_stub_loss,
                    prefill=None, decode=None, init_cache=None)
stub_batch = {
    "x": jnp.asarray(rng.standard_normal((8, n_p)).astype(np.float32)),
    "y": jnp.asarray(rng.standard_normal((8, n_p)).astype(np.float32)),
}
skip_comp = CompressionConfig(ratio=1.0, lanes=128, rows=6, chunk_blocks=8,
                              topk_ratio=0.1, topk_exact=True,
                              error_feedback=True, bucket_bytes=2 * 768 * 4,
                              rs_wire="native", stream_chunks=2)
stub_prof = ShardingProfile(tp_axis=None, vocab_axis=None, zero1=True)


def run_stub(rs_gather_skip):
    tc = TrainConfig(aggregator="compressed_rs", optimizer=opt,
                     compression=skip_comp, sharding=stub_prof,
                     remat="none", rs_gather_skip=rs_gather_skip)
    state = init_train_state(stub_api, tc, stub_mesh, jax.random.PRNGKey(0))
    step_fn, specs = build_train_step(stub_api, tc, stub_mesh)(state)
    _, bnamed = batch_specs(stub_batch, stub_mesh, tc)
    n_ag = str(jax.make_jaxpr(step_fn)(state, stub_batch)).count("all_gather")
    jitted = jax.jit(step_fn, in_shardings=(specs["named"], bnamed),
                     out_shardings=(specs["named"], None))
    st = jax.device_put(state, specs["named"])
    b = jax.device_put(stub_batch, bnamed)
    losses = []
    for _ in range(6):
        st, m = jitted(st, b)
        losses.append(float(m["loss"]))
    return losses, n_ag


l_skip, ag_skip = run_stub(True)
l_gather, ag_gather = run_stub(False)
print("stub skip    :", [round(x, 5) for x in l_skip], f"all_gathers={ag_skip}")
print("stub gather  :", [round(x, 5) for x in l_gather],
      f"all_gathers={ag_gather}")
assert ag_skip < ag_gather, (
    "gather-skip step did not drop the recovered-chunk all_gather: "
    f"{ag_skip} vs {ag_gather}")
assert all(abs(a - b) < 1e-5 for a, b in zip(l_skip, l_gather)), \
    f"gather-skip training diverged: {l_skip} vs {l_gather}"
assert l_skip[-1] < l_skip[0], "stub training loss must decrease"

# PR 8: the expert-parallel all-to-all exchange inside the real train
# step. On the full-manual leg the MoE combine routes each rank's
# expert-group partial sums through the dense / compressed exchange
# (W=2 over the profile's "model" EP axis; the executor pins the
# always-exact ratio=2.5 codec) and the stop_gradient splice keeps the
# backward pass on the local-combine cotangent — so training must be
# BIT-identical to the local combine, under Adam and, stricter, under
# the linear momentum optimizer. On the partial-auto leg the hook's
# full-manual gate leaves the local combine in place and the runs are
# trivially identical.
ep_comp = CompressionConfig(lanes=128, rows=6, chunk_blocks=8)


def run_ep(ep, o=opt):
    return run(TrainConfig(aggregator="dense", optimizer=o,
                           sharding=ShardingProfile(zero1=False),
                           remat="block", ep_exchange=ep,
                           compression=ep_comp))


l_ep_none = run_ep("none")
l_ep_dense = run_ep("dense")
l_ep_comp = run_ep("compressed")
print("ep none      :", [round(x, 4) for x in l_ep_none])
print("ep dense     :", [round(x, 4) for x in l_ep_dense])
print("ep compressed:", [round(x, 4) for x in l_ep_comp])
assert l_ep_none == l_ep_dense, \
    f"dense exchange diverged from local combine: {l_ep_none} vs {l_ep_dense}"
assert l_ep_none == l_ep_comp, \
    f"compressed exchange diverged from local combine: {l_ep_none} vs {l_ep_comp}"
assert l_ep_none[-1] < l_ep_none[0], "ep-exchange training must decrease"
l_epm_none = run_ep("none", opt_m)
l_epm_comp = run_ep("compressed", opt_m)
print("ep none (mom):", [round(x, 5) for x in l_epm_none])
print("ep comp (mom):", [round(x, 5) for x in l_epm_comp])
assert l_epm_none == l_epm_comp, \
    f"exchange diverged under momentum: {l_epm_none} vs {l_epm_comp}"
print("ALL OK")
