#!/usr/bin/env python3
"""Chip smoke test: Tri-Accel's trainer and server, once, on a TPU, at
smollm-135m's full published width (30 layers, d_model 576, 9 heads / 3 kv
heads of dim 64, vocab 49152; random weights from ``--seed``).

    python3 chip_smoke.py             # one chip: train, reference, serve
    python3 chip_smoke.py --chips 4   # four chips vs one: sharded trainer

Phases run in order in this one process and print one line each:

  device     the first JAX device must be a TPU (else exit 1 here)
  train      Trainer.warm_rungs() + Trainer.run(): Tri-Accel fully on —
             tpu ladder, fused resident update, flash attention, SR cast —
             through a precision-code refresh, a curvature refresh and a
             batch-rung switch; finite losses, no compile after warm-up,
             measured executable bytes, and the Pallas kernels present in
             every compiled step
  reference  one step of the fused/flash path against the jnp reference
             path (fused_update=False, chunked attention) from the same
             state and batch: loss and global grad norm within REF_RTOL
  serve      a ServeSession (one rung, tiers 0 and 1) answers 8 requests
             (prompt 256, 32 new tokens) through the ragged decode kernel
  chips4     (--chips 4 only, and nothing else) the trainer on a (4, 1)
             (data, model) mesh at microbatch 1 per chip against one chip
             at microbatch 4: losses over 5 steps within CHIPS4_RTOL

The last line of standard output is the result, and only when every phase
passed: {"ok": true, "device": {"platform", "kind", "count"}}. Any failure
raises and exits non-zero. Compiles persist in $JAX_COMPILATION_CACHE_DIR,
or in the checkout's .jax_cache/ when it is unset.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time
import warnings

import jax
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEQ = 2048
RUNGS = (2, 4)                 # both fit one v5e many times over (~3 GB)
STEPS = 10
T_CTRL, T_CURV = 4, 6          # code refresh at steps 4, 8; curvature at 6
PROMPT, NEW_TOKENS, REQUESTS, SERVE_RUNG = 256, 32, 8, 8
CACHE_LEN = 512                # ragged decode tiles it (128-slot blocks)

# Both paths run the same bf16 weights on the same batch; they differ only
# in f32 reduction order (flash vs chunked attention, kernel vs jnp update
# statistics). A reordered f32 sum can flip the bf16 rounding of an
# activation by one ulp (2^-8 relative); loss and grad norm average such
# flips over 8k tokens and ~135M gradient elements, so two ulps bounds them
# with room, while a masking, scaling or layout bug moves them by far more.
REF_RTOL = 2.0 ** -7
# Four chips see the same weights and global batch as one chip; their
# gradients are summed across chips in a different order (and rounded to
# bf16 per chip), which perturbs each update at the bf16-ulp level. Five
# small steps keep the loss within the same two-ulp bound.
CHIPS4_RTOL = 2.0 ** -7

KERNELS_TRAIN = {"flash_attention_fwd", "flash_attention_bwd", "fused_stats",
                 "fused_apply"}
KERNEL_DECODE = "flash_decode"


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def kernels_in(exe) -> set:
    """Pallas kernels (by their jitted wrapper's name) compiled into an
    executable's TPU custom calls."""
    out = set()
    for line in exe.as_text().splitlines():
        if "tpu_custom_call" in line:
            m = re.search(r"jit\((\w+)\)/pallas_call", line)
            if m:
                out.add(m.group(1))
    return out


# ------------------------------------------------------------------ phases --
def device_phase(chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"[device] FAILED: first JAX device is {d.platform!r}, not a "
              "TPU", file=sys.stderr)
        sys.exit(1)
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    say("device", f"ok platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def smollm_task(impl: str = "flash"):
    from repro.configs import smollm_135m
    from repro.train.task import LMTask
    cfg = smollm_135m.config()
    st = cfg.stack
    check((st.num_layers, st.d_model, st.attn.num_heads,
           st.attn.num_kv_heads, st.attn.head_dim, cfg.vocab_size)
          == (30, 576, 9, 3, 64, 49152), "smollm-135m is not at its "
          "published width")
    if impl != st.attn.impl:
        cfg = dataclasses.replace(cfg, stack=dataclasses.replace(
            st, attn=dataclasses.replace(st.attn, impl=impl)))
    return LMTask(cfg)


def make_trainer(task, mesh, *, rungs, start_rung, seed, sr=True,
                 curvature=True, batch=True):
    from repro.core.precision import TriAccelConfig
    from repro.train.trainer import Trainer, TrainerConfig
    tac = TriAccelConfig(ladder="tpu", stochastic_round=sr, t_ctrl=T_CTRL,
                         t_curv=T_CURV, curvature_method="fisher",
                         enable_curvature=curvature, enable_batch=batch)
    tcfg = TrainerConfig(total_steps=100, base_lr=3e-3, warmup_steps=2,
                         optimizer="sgdm", grad_clip=1.0, seed=seed,
                         seq_len=SEQ, rungs=rungs, start_rung=start_rung,
                         log_every=1, b_curv=2)
    return Trainer(task, tac, tcfg, mesh=mesh)


def train_phase(seed: int):
    from repro.core.batch_scaler import measured_exe_bytes
    from repro.core.precision import codes_from_stats
    from repro.launch.mesh import make_dev_mesh
    task = smollm_task()
    tr = make_trainer(task, make_dev_mesh(jax.devices()[:1]), rungs=RUNGS,
                      start_rung=RUNGS[0], seed=seed)
    check(tr.resident and tr.fused, "fused resident update is not on")
    t0 = time.time()
    tr.warm_rungs()
    warm_s = time.time() - t0
    warmed = tr.compile_count
    check(warmed == len(RUNGS), f"{warmed} executables for {RUNGS}")
    for key, exe in tr._executables.items():
        mb = measured_exe_bytes(exe)
        check(mb is not None and mb > 0, f"no measured bytes for {key[0]}")
        missing = KERNELS_TRAIN - kernels_in(exe)
        check(not missing, f"rung {key[0]} step lacks kernels {missing}")
    gb = [round(v / 1e9, 3) for v in tr.measured_bytes.values()]
    say("train", f"warm_rungs {warm_s:.1f}s for rungs {RUNGS}, measured "
        f"bytes {gb} GB, kernels {sorted(KERNELS_TRAIN)} in every step")

    codes0 = np.asarray(tr.state.control.codes)
    refreshed, lam_set = [], False
    t0 = time.time()
    for _ in range(STEPS):
        tr.run(1)
        ctl = jax.device_get(tr.state.control)
        step = int(ctl.step)
        if step % T_CTRL == 0:
            want = np.asarray(codes_from_stats(ctl.var_ema, ctl.lam, tr.tac))
            check(np.array_equal(np.asarray(ctl.codes), want),
                  f"codes at step {step} are not a refresh of var_ema")
            refreshed.append(step)
        lam_set |= bool(np.any(np.asarray(ctl.lam) != 0))
    run_s = time.time() - t0
    log = tr.metrics_log
    losses = [m["loss"] for m in log]
    rungs_seen = [m["rung"] for m in log]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(tr.compile_count == warmed,
          f"{tr.compile_count - warmed} compiles after warm-up")
    check(refreshed and not np.array_equal(np.asarray(ctl.codes), codes0),
          "no precision-code refresh changed the codes")
    check(lam_set and np.all(np.isfinite(np.asarray(ctl.lam))),
          "no curvature refresh")
    check(len(set(rungs_seen)) > 1, f"no rung switch: {rungs_seen}")
    say("train", f"ok {STEPS} steps in {run_s:.1f}s, losses "
        f"{[round(x, 4) for x in losses]}, rungs {rungs_seen}, code "
        f"refresh at steps {refreshed} (codes {codes0.tolist()} -> "
        f"{np.asarray(ctl.codes).tolist()}), curvature lam "
        f"{np.round(np.asarray(ctl.lam), 4).tolist()}, compiles after "
        f"warm-up 0")
    return tr, warm_s


def reference_phase(tr):
    """One fused/flash step vs one reference step from the same weights:
    the state's master is set to its own compute copy and every code to 2,
    so both forwards see bit-identical bf16 weights and no tier rounding."""
    import jax.numpy as jnp
    from repro.train.schedules import warmup_cosine
    from repro.train.train_step import make_train_step, pack_state
    rung = tr.scaler.microbatch
    step = int(tr.state.control.step)
    batch = tr._batch_for_rung(rung, step)
    tree = tr._save_state()
    comp = tree.compute["tree"]
    tree = tree._replace(
        params=jax.tree.map(lambda c: c.astype(jnp.float32), comp),
        control=tree.control._replace(
            codes=jnp.full_like(tree.control.codes, 2)))
    ref_step = jax.jit(make_train_step(
        smollm_task(impl="chunked"), tr.tac, tr.opt, tr.grouping,
        warmup_cosine(tr.tcfg.base_lr, tr.tcfg.warmup_steps,
                      tr.tcfg.total_steps),
        grad_clip=tr.tcfg.grad_clip, fused_update=False))
    _, mr = ref_step(tree._replace(compute=()), batch)
    # the warmed step donates its state: hand it copies, never the trainer's
    fused_state = tr._place_resident(jax.tree.map(
        jnp.copy, pack_state(tr.view, tree, tr.task.compute_dtype)))
    _, mf = tr._get_step(rung)(fused_state, batch)
    mf, mr = jax.device_get((mf, mr))
    for k in ("loss", "grad_norm"):
        got, want = float(mf[k]), float(mr[k])
        rel = abs(got - want) / abs(want)
        check(math.isfinite(got) and rel <= REF_RTOL,
              f"{k}: fused/flash {got} vs reference {want} (rel {rel:.2e} "
              f"> {REF_RTOL:.2e})")
        say("reference", f"{k} fused/flash {got:.6f} reference {want:.6f} "
            f"rel {rel:.2e} <= {REF_RTOL:.2e}")
    say("reference", "ok")


def serve_phase(tr, seed: int):
    from repro.kernels import ops
    from repro.serve.session import ServeConfig, ServeSession
    cfg = ServeConfig(prompt_len=PROMPT, total_len=CACHE_LEN,
                      rungs=(SERVE_RUNG,), tiers=(0, 1), ladder="tpu",
                      max_new_tokens=NEW_TOKENS, auto_tier=False, seed=seed)
    attn = tr.task.cfg.stack.attn
    check(ops.flash_decode_gate((SERVE_RUNG, 1, attn.num_heads,
                                 attn.head_dim),
                                (SERVE_RUNG, CACHE_LEN, attn.num_kv_heads,
                                 attn.head_dim), None),
          "cache length fails the ragged decode gate")
    sess = ServeSession(tr.task, cfg, params=tr.params_tree())
    t0 = time.time()
    n = sess.warm()
    warm_s = time.time() - t0
    for tier in (0, 1):
        found = kernels_in(sess.engine._exe[("decode", SERVE_RUNG, tier)])
        check(KERNEL_DECODE in found,
              f"tier {tier} decode executable lacks {KERNEL_DECODE}: {found}")
    vocab = tr.task.cfg.vocab_size
    rng = np.random.default_rng(seed)
    rids = []
    for tier in (0, 1):
        sess.set_tier(tier)
        for _ in range(REQUESTS // 2):
            rids.append(sess.submit(
                {"tokens": rng.integers(0, vocab, PROMPT, dtype=np.int32)}))
        sess.run()
    res = sess.results()
    done = [res[r] for r in rids if res[r].status == "done"]
    check(len(done) == REQUESTS, f"{len(done)}/{REQUESTS} requests done")
    check(all(len(r.tokens) == NEW_TOKENS
              and all(0 <= t < vocab for t in r.tokens) for r in done),
          "a request came back with the wrong number of tokens or ids")
    check(sess.compile_count == n,
          f"{sess.compile_count - n} serve compiles after warm-up")
    say("serve", f"ok {len(done)} requests x {NEW_TOKENS} tokens over tiers "
        f"{[t for _, t in sess.tier_history]} at rung {SERVE_RUNG}, "
        f"warm {warm_s:.1f}s ({n} executables), {KERNEL_DECODE} in every "
        f"decode executable, compiles after warm-up 0")


def chips4_phase(seed: int, steps: int = 5):
    from repro.launch.mesh import make_dev_mesh
    task = smollm_task()
    devs = jax.devices()
    runs = {}
    for label, mesh, mb in (("4 chips x mb 1", make_dev_mesh(devs[:4]), 1),
                            ("1 chip x mb 4", make_dev_mesh(devs[:1]), 4)):
        tr = make_trainer(task, mesh, rungs=(mb,), start_rung=mb, seed=seed,
                          sr=False, curvature=False, batch=False)
        check(tr.slab_shards == mesh.size, f"slab_shards {tr.slab_shards}")
        t0 = time.time()
        tr.warm_rungs()
        warm_s = time.time() - t0
        tr.run(steps)
        runs[label] = [m["loss"] for m in tr.metrics_log]
        say("chips4", f"{label}: slab_shards {tr.slab_shards}, warm "
            f"{warm_s:.1f}s, losses {[round(x, 5) for x in runs[label]]}")
        del tr
    a, b = runs.values()
    rel = max(abs(x - y) / abs(y) for x, y in zip(a, b))
    check(all(math.isfinite(x) for x in a + b), "non-finite loss")
    check(rel <= CHIPS4_RTOL, f"4-chip vs 1-chip loss rel {rel:.2e} > "
          f"{CHIPS4_RTOL:.2e}")
    say("chips4", f"ok max rel loss difference {rel:.2e} <= "
        f"{CHIPS4_RTOL:.2e} over {steps} steps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_phase(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    say("device", f"compile cache {enable_compile_cache()}")
    # a kernel-gate fallback would silently time the jnp path instead
    warnings.filterwarnings("error", message="flash_attention: kernel gate")
    t0 = time.time()
    if args.chips == 4:
        chips4_phase(args.seed)
    else:
        tr, _ = train_phase(args.seed)
        reference_phase(tr)
        serve_phase(tr, args.seed)
    say("done", f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
