#!/usr/bin/env python3
"""Readings that the correctness limits of a training cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--fault <name> --fault-seeds 3] [--out <file.jsonl>]

In one process on the chip, for each seed, the program's first steps at the
cell's own size against the plain reference: as the configuration states
(the lower reading), under the configuration's control (the upper reading),
and, with ``--fault``, broken underneath (``bench/faults.py``). Each kind
takes seeds of its own. Nothing is timed. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def numbers(prog, ref) -> dict:
    from bench import reference
    nums = reference.numbers(prog, ref)
    return {k: v for k, (v, _) in nums.items()} | {
        k + "_at": w for k, (_, w) in nums.items()}


def train_reading(cell, seed, devices, codes, known_oom):
    from bench import drive_train
    tr = drive_train.build(cell, seed, devices, codes)
    drive_train.warm_reachable(tr, lambda m: None, known_oom=known_oom)
    prog = drive_train.first_steps(tr, int(cell.traffic["trainer"]
                                           ["log_every"]))
    del tr
    gc.collect()
    ref = drive_train.reference_numbers(cell, seed, prog["batches"])
    out = numbers(prog, ref)
    out["leaves"] = {k: [prog["grad_norms"][k], ref["grad_norms"][k],
                         prog["delta_norms"][k], ref["delta_norms"][k],
                         ref["raw_grad_norms"][k]] for k in ref["grad_norms"]}
    out.update(prog_losses=prog["losses"], ref_losses=ref["losses"],
               rungs=prog["rungs"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=900001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import drive_train, faults
    from bench.run import Cell, check_device, read_json
    cell = Cell(read_json(os.path.join(ROOT, "BENCHMARK.json")),
                args.workload)
    devs = check_device(cell.chips)
    out = open(args.out, "a") if args.out else sys.stdout
    runs = [("program", None, None, args.seeds),
            ("control", drive_train.control_codes(cell), None,
             args.control_seeds)]
    runs += [(f"fault:{f}", None, f, args.fault_seeds) for f in args.fault]
    seed, known_oom = args.first_seed, set()
    for kind, pin, fault, n in runs:
        for _ in range(n):
            t0 = time.time()
            restore = faults.plant_train(fault) if fault else None
            try:
                r = train_reading(cell, seed, devs, pin, known_oom)
            finally:
                if restore:
                    restore()
            r.update(kind=kind, seed=seed, workload=args.workload,
                     seconds=round(time.time() - t0, 1))
            print(json.dumps(r), file=out, flush=True)
            seed += 7919
    return 0


if __name__ == "__main__":
    sys.exit(main())
