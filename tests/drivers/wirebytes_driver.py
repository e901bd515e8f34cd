"""Multi-device driver (PR 6 satellite): cross-check the analytic wire
accounting (``CompressionConfig.strategy_wire_bytes``) against the bytes
the launched collectives actually move, counted off the jaxpr with the
benchmark's ``_count_link_bytes`` model — at W=2 and W=4, for all four
fixed strategies.

Per strategy:

- ``dense``                 — per-link bytes of the leaf psums must equal
  ``link_bytes`` exactly (ring AllReduce, ``2(W-1)/W x`` payload).
- ``compressed``            — sketch psum at the ring factor plus the
  bitmap OR-ring at the same factor; the total equals ``link_bytes``
  exactly.
- ``compressed_rs`` native  — psum_scatter sketch + OR-Reduce-Scatter
  bitmap + recovered-chunk all_gather; it must equal ``link_bytes``
  (gather included) exactly.
- ``compressed_rs`` emulate — AllReduce wire (psum + local slice): same
  expected bytes as ``compressed`` — plus the recovered-chunk all_gather
  the implementation launches to reassemble the per-rank peeled chunks.
  The analytic entry deliberately models only the AllReduce wire
  (``compressed_rs_emulated == compressed``, pinned by
  test_collectives), so the gather term is added here from the native
  entry's ``rs_gather_link_bytes`` (same collective, same bytes).
- ``compressed_innet``      — its analytic numbers model the *switch
  tree* (payload crosses each link once), which the in-mesh ppermute
  emulation cannot reproduce (reduce-to-root reships the payload per
  tier). Cross-checked instead by (a) the wire-model self-consistency
  ``link_bytes == rank_payload_bytes == root_link_bytes`` (+ per-bucket
  exponent metadata on the fxp32 wire only), and (b) the f32 arm's
  output being bit-identical to ``compressed`` (same payload objects on
  the wire).

The stream is sized so the packed bitmap is >= 64 KiB: above
``or_allreduce``'s ring threshold, so the ppermute leg takes the
bandwidth-optimal ring at W=4 (recursive doubling would cost
``log2(W) x`` instead).
"""
import os
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_force_host_platform_device_count=8 "
    "--xla_disable_hlo_passes=all-reduce-promotion")
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
from benchmarks.aggregation import _count_collectives, _count_link_bytes

from repro import compat
from repro.core import CompressionConfig
from repro.core import collectives as coll
from repro.core.aggregators import make_aggregator
from repro.core.collectives import AggregationState

# block_elems = round(6/0.3)*128 = 2560; 16 buckets of 16 blocks each.
# Total 655360 elems -> packed bitmap = 655360/8 = 81920 bytes >= 64 KiB
# (forces the OR-ring on the ppermute leg), and the 20480 bitmap words
# divide evenly into W in {2, 4} ring chunks (no ring padding slack).
N = 2560 * 16 * 16
cfg = CompressionConfig(ratio=0.3, lanes=128, rows=6, rounds=10,
                        chunk_blocks=64, use_pallas="never",
                        bucket_bytes=2560 * 16 * 4)



def dyadic(n, seed, frac=0.03):
    r = np.random.default_rng(seed)
    x = np.zeros(n, np.float32)
    k = int(n * frac)
    idx = r.choice(n, size=k, replace=False)
    x[idx] = (r.choice([-1.0, 1.0], size=k)
              * np.exp2(r.integers(-2, 3, size=k))).astype(np.float32)
    return x


for W in (2, 4):
    mesh = compat.make_mesh((W,), ("data",), devices=jax.devices()[:W])
    tree = {"g": dyadic(N, seed=0)}
    stacked = {"g": jnp.asarray(np.stack(
        [dyadic(N, seed=w) for w in range(W)]))}
    put = jax.device_put(stacked, NamedSharding(mesh, P("data", None)))
    in_specs = {"g": P("data", None)}
    out_specs = {"g": P()}

    acc = cfg.strategy_wire_bytes(N, W, grad_bytes_per_elem=4)
    wb = cfg.wire_bytes(N, grad_bytes_per_elem=4)
    nb = wb["n_buckets"]
    sketch_full = nb * wb["bucket_sketch_bytes"]
    idx_full = nb * wb["bucket_index_bytes"]
    ring = 2 * (W - 1) / W

    def jaxpr_of(name, rs_wire="auto", wire_dtype="f32"):
        import dataclasses
        cfg_a = dataclasses.replace(cfg, rs_wire=rs_wire,
                                    wire_dtype=wire_dtype)
        agg = make_aggregator(name, cfg_a, mesh, ("data",), (),
                              outer_manual=("data",))

        def path(grads):
            specs = {"g": P()}
            res = coll.init_aggregation_state(grads, cfg_a).residual
            out, _ = agg(grads, AggregationState(residual=res), specs)
            return out

        fn = jax.jit(compat.shard_map(
            lambda st: path(jax.tree.map(lambda a: a[0], st)),
            mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
            axis_names={"data"}, check_vma=False))
        return fn, jax.make_jaxpr(fn)(put)

    # ---- dense: exact ------------------------------------------------
    _, jx = jaxpr_of("dense")
    got = _count_link_bytes(jx, W)
    want = acc["dense"]["link_bytes"]
    assert round(got) == want, (W, "dense", got, want)
    print(f"OK W={W} dense: measured {round(got)} == analytic {want}")

    # ---- compressed + emulated RS: AllReduce wire --------------------
    emu_gather = acc["compressed_rs_native"]["rs_gather_link_bytes"]
    for name, rs_wire, key, extra in (
            ("compressed", "auto", "compressed", 0),
            ("compressed_rs", "emulate", "compressed_rs_emulated",
             emu_gather)):
        _, jx = jaxpr_of(name, rs_wire=rs_wire)
        got = _count_link_bytes(jx, W)
        want = ring * (sketch_full + idx_full) + extra
        assert round(got) == round(want), (W, key, got, want)
        # taking the emulated arm's recovered-chunk gather back out of
        # the traffic must recover the analytic link accounting
        assert abs(got - extra - acc[key]["link_bytes"]) <= 1, \
            (W, key, got - extra, acc[key]["link_bytes"])
        print(f"OK W={W} {key}: measured {round(got)} == "
              f"sketch*ring + index*ring"
              + (f" + gather {extra}" if extra else "")
              + f" (analytic {acc[key]['link_bytes']})")

    # ---- native RS: ppermute wire, exact ------------------------------
    _, jx = jaxpr_of("compressed_rs", rs_wire="native")
    got = _count_link_bytes(jx, W)
    want = acc["compressed_rs_native"]["link_bytes"]
    assert round(got) == want, (W, "rs_native", got, want)
    counts = _count_collectives(jx, {})
    assert any(k.startswith(("psum_scatter", "reduce_scatter"))
               for k in counts), counts
    print(f"OK W={W} compressed_rs_native: measured {round(got)} == "
          f"analytic {want} (incl. gather)")
    # rank payload really is the 1/W slice
    assert acc["compressed_rs_native"]["rank_payload_bytes"] \
        == (sketch_full + idx_full) // W

    # ---- innet: model self-consistency + f32 == compressed -----------
    for wd in ("f32", "fxp32"):
        import dataclasses
        acc_w = dataclasses.replace(cfg, wire_dtype=wd).strategy_wire_bytes(
            N, W, grad_bytes_per_elem=4)
        e = acc_w["compressed_innet"]
        assert e["link_bytes"] == e["rank_payload_bytes"] \
            == e["root_link_bytes"], (W, wd, e)
        assert e["exponent_bytes"] == (nb * 4 if wd == "fxp32" else 0)
        assert e["rank_payload_bytes"] == sketch_full + idx_full \
            + e["exponent_bytes"]
    fn_c, _ = jaxpr_of("compressed")
    fn_i, _ = jaxpr_of("compressed_innet")
    out_c = np.asarray(fn_c(put)["g"])
    out_i = np.asarray(fn_i(put)["g"])
    assert np.array_equal(out_c, out_i), \
        "innet f32 output diverged from compressed"
    print(f"OK W={W} compressed_innet: wire model self-consistent, "
          "f32 arm == compressed bitwise")

    # ---- all-to-all exchange (PR 8): W-1 permute lanes, exact --------
    # The mesh's single manual axis makes the region full-manual, so the
    # native ppermute wire runs on BOTH legs and each rank ships exactly
    # (W-1)/W of its stacked payload: the analytic *_alltoall entries
    # must match the jaxpr-counted bytes with no emulation factor. N/W
    # fills the per-destination bucket grid exactly (no padding slack).
    from repro.core.aggregators import make_exchange
    n_d = N // W
    assert n_d % cfg.bucket_elems_for(n_d) == 0
    a2a_payload = {"g": jnp.asarray(np.stack(
        [dyadic(n_d, seed=100 + w) for w in range(W)]))}
    for wire in ("dense_alltoall", "compressed_alltoall"):
        ex = make_exchange(wire.split("_")[0], cfg, mesh, ("data",))
        fn = jax.jit(compat.shard_map(
            lambda p, ex=ex: jax.tree.map(lambda l: l[None], ex(p)),
            mesh=mesh, in_specs=({"g": P()},),
            out_specs={"g": P("data", None)},
            axis_names={"data"}, check_vma=False))
        jx = jax.make_jaxpr(fn)(a2a_payload)
        got = _count_link_bytes(jx, W)
        want = acc[wire]["link_bytes"]
        assert round(got) == want, (W, wire, got, want)
        print(f"OK W={W} {wire}: measured {round(got)} == analytic {want}")
    assert acc["compressed_alltoall"]["rank_payload_bytes"] \
        < acc["dense_alltoall"]["rank_payload_bytes"], \
        "compressed a2a must undercut dense per-rank bytes at this ratio"

print("ALL OK")
