"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --seq 2048 --rungs 2,4,8 --ckpt ckpt/

On a real TPU slice this process runs per host (jax.distributed initializes
from the TPU environment); otherwise it trains data-parallel over every
local device (one on CPU). SIGTERM checkpoints and exits; rerunning
resumes. The default sequence length is a multiple of the flash kernel's
block, so attention runs the Pallas kernels rather than the jnp fallback.
Compiles persist in ``$JAX_COMPILATION_CACHE_DIR`` or the checkout's
``.jax_cache/``. Use
repro.launch.dryrun (separate entry point, forces 512 host devices) for
the production-mesh compile-only pass.
"""
from __future__ import annotations

import argparse
import json
import os

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--rungs", default="2,4,8")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ladder", default="tpu", choices=["tpu", "gpu"])
    ap.add_argument("--optimizer", default="sgdm", choices=["sgdm", "adamw"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--mem-cap-gb", type=float, default=None,
                    help="per-device memory cap (default: the device's "
                         "own limit)")
    ap.add_argument("--no-triaccel", action="store_true",
                    help="static bf16 baseline (AMP) instead of Tri-Accel")
    ap.add_argument("--distributed", action="store_true",
                    help="jax.distributed.initialize() from env (TPU slice)")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.core.precision import TriAccelConfig
    from repro.models.registry import get_task
    from repro.train.trainer import Trainer, TrainerConfig

    task = get_task(args.arch, reduced=args.reduced)
    tac = TriAccelConfig(
        ladder=args.ladder, t_ctrl=20, t_curv=100, b_curv=2,
        curvature_method="fisher",
        mem_cap_bytes=None if args.mem_cap_gb is None
        else args.mem_cap_gb * 1e9,
        enable_precision=not args.no_triaccel,
        enable_curvature=not args.no_triaccel,
        enable_batch=not args.no_triaccel,
        dynamic_precision=not args.no_triaccel)
    rungs = tuple(int(r) for r in args.rungs.split(","))
    tcfg = TrainerConfig(total_steps=args.steps, base_lr=args.lr,
                         warmup_steps=max(10, args.steps // 20),
                         optimizer=args.optimizer, accum=args.accum,
                         seq_len=args.seq, rungs=rungs, ckpt_dir=args.ckpt,
                         ckpt_every=max(50, args.steps // 10), log_every=10)
    tr = Trainer(task, tac, tcfg)
    tr.install_preemption_handler()
    tr.warm_rungs()
    start = tr.maybe_restore()
    if start:
        print(f"resumed at step {start}", flush=True)
    log = tr.run(args.steps - start)
    for m in log:
        print(json.dumps({k: (round(v, 5) if isinstance(v, float) else v)
                          for k, v in m.items()}), flush=True)


if __name__ == "__main__":
    main()
