"""Serving launcher: batched greedy generation on a host mesh, plus the
elastic aggregation service (PR 9) driven against the same model.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --batch 4 --prompt-len 16 --max-new 32

    # elastic: async sketch-fold rounds over an intermittent cohort,
    # using the arch's parameter tree as the gradient template
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --elastic --cohort 4 --rounds 3 --wire fxp32
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def run_elastic(args, cfg, params):
    """Round-driven elastic aggregation over the arch's gradient tree.

    Each round: open a contract for the live cohort, have every client
    contribute a synthetic gradient for the *model's own parameter
    shapes*, fold payloads in arrival order (with injected stragglers
    when asked), close at quorum/deadline. A client joins mid-run so the
    fxp32 wire renegotiates its mantissa budget at least once.
    """
    import dataclasses
    import jax
    from repro.core.config import CompressionConfig
    from repro.elastic import AdmissionPolicy, ElasticClient, ElasticServer
    from repro.ft.failures import FailureSimulator, SwitchRetransmitPolicy

    template = jax.tree.map(np.asarray, params)
    ccfg = CompressionConfig(ratio=1.0, lanes=128, rows=6, rounds=10,
                             chunk_blocks=8, topk_ratio=0.1,
                             topk_exact=True, error_feedback=True,
                             wire_dtype=args.wire)
    policy = AdmissionPolicy(max_cohort=max(args.cohort + 1, 4),
                             quorum=0.5, deadline_s=args.deadline)
    sim = FailureSimulator(
        straggle_at=(((1, 0, args.deadline * 5),) if args.straggle else ()))
    srv = ElasticServer(template, ccfg, policy=policy,
                        retransmit=SwitchRetransmitPolicy())
    clients = {}

    def admit(c):
        srv.join(c)
        clients[c] = ElasticClient(c, ccfg)

    for c in range(args.cohort):
        admit(c)

    rng = np.random.default_rng(0)
    for rnd in range(args.rounds):
        if rnd == args.rounds // 2:    # membership churn mid-run
            admit(args.cohort)
        contract = srv.open_round()
        roster = contract.cohort
        grads = {c: jax.tree.map(
            lambda a: rng.normal(0, 1, a.shape).astype(np.float32),
            template) for c in roster}
        if ccfg.wire_dtype == "fxp32":
            for c in roster:
                srv.submit_exponents(clients[c].propose(contract, grads[c]))
            shared = srv.seal_exponents()
            payloads = {c: clients[c].payload(contract, shared)
                        for c in roster}
        else:
            payloads = {c: clients[c].contribute(contract, grads[c])
                        for c in roster}
        t0 = time.perf_counter()
        for c in roster:
            arrival = 0.001 * (c + 1) + sim.client_delay(rnd, c)
            srv.submit(payloads[c], arrival_s=arrival)
        stream = srv.close_round(now_s=args.deadline)[0]
        dt = time.perf_counter() - t0
        rep = srv.reports[-1]
        m = contract.mantissa_bits
        print(f"round {rep.round_id}: W={rep.workers} "
              f"wire={contract.wire_dtype}"
              f"{'' if m is None else f'/M={m}'} "
              f"folded={rep.folded} deferred={rep.deferred} "
              f"retransmits={rep.retransmits} close={rep.close_reason} "
              f"fold={dt*1e3:.1f}ms |out|={float(np.abs(stream).max()):.3g}")
    total = sum(r.folded + r.deferred for r in srv.reports)
    print(f"elastic: {len(srv.reports)} rounds, {total} payloads "
          f"accounted (0 lost)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="drive the continuous batcher instead")
    ap.add_argument("--elastic", action="store_true",
                    help="run elastic aggregation rounds over the "
                         "arch's gradient tree instead of serving")
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--wire", choices=["f32", "fxp32"], default="f32")
    ap.add_argument("--deadline", type=float, default=1.0)
    ap.add_argument("--straggle", action="store_true",
                    help="inject one past-deadline straggler (deferred "
                         "into the next round's residual)")
    args = ap.parse_args()

    import jax
    from repro.configs import get_arch
    from repro.models import model_api
    from repro.serve import ServeEngine, ContinuousBatcher, Request
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.model
    api = model_api(cfg)
    params = api.init(jax.random.PRNGKey(0))

    if args.elastic:
        run_elastic(args, cfg, params)
        return

    max_len = args.max_len or (args.prompt_len + args.max_new + 8)
    eng = ServeEngine(api, params, max_len=max_len, batch=args.batch)

    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, (args.batch, args.prompt_len),
                           dtype=np.int32)
    extra = {}
    if cfg.family == "encdec":
        extra["frames"] = rng.normal(
            0, 1, (args.batch, cfg.enc_seq, cfg.d_model)).astype(np.float32)

    if args.continuous:
        cb = ContinuousBatcher(eng)
        for u in range(args.batch * 2):
            cb.submit(Request(uid=u, prompt=prompts[u % args.batch],
                              max_new_tokens=args.max_new))
        t0 = time.perf_counter()
        done = cb.run(decode_steps=args.max_new * 3)
        dt = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in done)
        print(f"continuous: {len(done)} requests, {toks} tokens "
              f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")
        return

    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new, extra=extra or None)
    dt = time.perf_counter() - t0
    toks = out.size
    print(f"batch generate: {out.shape} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")
    print("first row:", out[0][:16].tolist())


if __name__ == "__main__":
    main()
