"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
        --steps 200 --global-batch 8 --seq-len 128 --aggregator compressed

``--smoke`` selects the reduced same-family config (the full configs need
the production pod). ``--n-layers N`` keeps the published config and cuts
its depth alone — widths, heads, vocab and compression stay as published
— so a published-width model fits one chip:

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --n-layers 4 --steps 3 --global-batch 8 --seq-len 4096

The host mesh spreads over whatever devices exist (data x model via
--model-parallel). The persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os


def select_model(arch, smoke: bool = False, n_layers=None):
    """The model config a run trains: the published one (or the reduced
    ``smoke`` one), with only ``n_layers`` replaced when given."""
    cfg = arch.smoke if smoke else arch.model
    if n_layers is not None:
        if n_layers < 1:
            raise ValueError(f"n_layers must be >= 1, got {n_layers}")
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the config's depth to N layers; every "
                         "width stays as configured")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--aggregator",
                    choices=["dense", "compressed", "compressed_rs",
                             "compressed_innet"],
                    default=None)
    ap.add_argument("--compression-ratio", type=float, default=None)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0,
                    help="fake host devices (0 = real)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            "--xla_disable_hlo_passes=all-reduce-promotion "
            + os.environ.get("XLA_FLAGS", ""))

    from repro.configs import get_arch
    from repro.models import model_api
    from repro.train.loop import run_training
    from repro.launch.cache import use_compile_cache
    from repro.launch.mesh import make_host_mesh

    use_compile_cache()
    arch = get_arch(args.arch)
    cfg = select_model(arch, args.smoke, args.n_layers)
    tc = arch.train
    if args.aggregator:
        tc = dataclasses.replace(tc, aggregator=args.aggregator)
    if args.compression_ratio:
        tc = dataclasses.replace(tc, compression=dataclasses.replace(
            tc.compression, ratio=args.compression_ratio))
    if args.lr:
        tc = dataclasses.replace(tc, optimizer=dataclasses.replace(
            tc.optimizer, lr=args.lr, total_steps=args.steps))
    if args.smoke:
        # reduced runs don't need 8-way accumulation or remat
        tc = dataclasses.replace(tc, accum_steps=1, remat="none")

    mesh = make_host_mesh(model_parallel=args.model_parallel)
    api = model_api(cfg)
    res = run_training(api, tc, mesh, global_batch=args.global_batch,
                       seq_len=args.seq_len, steps=args.steps,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    print(json.dumps({
        "arch": args.arch, "aggregator": tc.aggregator,
        "n_layers": cfg.n_layers,
        "cut": ({"n_layers": [(arch.smoke if args.smoke else arch.model)
                              .n_layers, cfg.n_layers]}
                if args.n_layers is not None else None),
        "first_loss": res.losses[0], "last_loss": res.losses[-1],
        "restarts": res.restarts, "steps": res.final_step,
    }, indent=1))


if __name__ == "__main__":
    main()
