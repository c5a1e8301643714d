"""Driver of the training cells: one ``Trainer`` built from the seed, driven
through its first steps (checked against the plain reference), then timed
for the window in chunks of whole control periods.

Set-up builds the configuration's weights on the device from the seed,
hands them to the program's ``Trainer``, warms the step executable of every
rung the batch scaler can reach from its own pick, drives the first steps
through ``Trainer.run`` with the metrics of each step logged, and warms the
eager curvature refresh. The window then calls ``Trainer.run(t_ctrl)``
until ``--seconds`` have passed and ends on ``block_until_ready``. After
the window the program's state is freed and the reference follows the
first steps from the same seed.

The window's first chunk ends on the first control tick after the checked
steps, and every later chunk on the next tick, so that at each chunk's end
the precision codes were just chosen: the harness reads them with the
variance and curvature they were chosen from, and counts every layer
whose code departs from the configuration's threshold rule.
"""
from __future__ import annotations

import contextlib
import copy
import gc
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench import trace as bench_trace
from bench.harness_util import (CompileCounter, derived_seed, kernels_in,
                                seed_key, seeded_task)

CHECK_STEPS = 3


def rung_ladder(task, traffic, tac, params_like) -> tuple:
    """Doubling rungs from ``rung_base`` up to and including the first rung
    the program's memory model refuses (or ``rung_max``, which only the
    small stand-ins of the tests set)."""
    mm = task.memory_model(params_like, opt_slots=1)
    per = task.tokens_per_sample(int(traffic.get("seq_len", 1)))
    cap = tac.rho_high * tac.mem_cap_bytes
    r, out = int(traffic["rung_base"]), []
    top = traffic.get("rung_max")
    while True:
        out.append(r)
        if mm.total(r * per, None, tac.ladder) > cap:
            break
        if top is not None and r >= int(top):
            break
        r *= 2
    return tuple(out)


def warm_reachable(tr, log, ticks: int = 8, known_oom=None) -> list:
    """Compile the scaler's pick and every rung a copy of the scaler moves
    to over ``ticks`` control ticks on the measured footprints; also run
    each rung's eager batch builder once. Rungs in ``known_oom`` (a set the
    caller keeps across builds in one process) are poisoned without being
    compiled again; rungs found not to fit are added to it."""
    from repro.resilience.faults import is_oom_error
    warmed = []
    for r in sorted(known_oom or ()):
        tr.scaler.mark_oom(r)

    def warm(r):
        tr._get_step(r)
        jax.block_until_ready(tr._batch_for_rung(r, 0))
        warmed.append(r)

    while True:
        r = tr.scaler.microbatch
        try:
            warm(r)
            break
        except Exception as e:          # noqa: BLE001 — filtered below
            # the program's own recovery: a rung whose step does not fit
            # is poisoned and the scaler steps down (Trainer._dispatch)
            if not is_oom_error(e) or tr.scaler.mark_oom(r) == r:
                raise
            if known_oom is not None:
                known_oom.add(r)
            log(f"rung {r} does not fit the chip ({str(e).splitlines()[0]}"
                f"); the scaler steps down to {tr.scaler.microbatch}")
    sim = copy.deepcopy(tr.scaler)
    for i in range(ticks):
        sim.model.measured.update(tr.scaler.model.measured)
        key = (sim.microbatch, jax.tree_util.tree_structure(tr.state))
        sim.observe(i, measured_bytes=tr.measured_bytes.get(key))
        if sim.microbatch not in warmed:
            warm(sim.microbatch)
    log(f"warmed rungs {warmed} of ladder {tuple(tr.tcfg.rungs)}; measured "
        f"bytes {[round(v / 1e9, 3) for v in tr.measured_bytes.values()]} GB")
    return warmed


def build(cell, seed, devices, force_codes=None):
    """The Trainer for ``cell`` on ``devices`` from ``seed``."""
    from repro.core.batch_scaler import with_device_cap
    from repro.core.precision import TriAccelConfig
    from repro.launch.mesh import make_dev_mesh
    from repro.train.trainer import Trainer, TrainerConfig
    c, tf, mod = cell.config, cell.traffic, cell.config_module
    task = mod.make_task(c)
    params, aux = mod.init_weights(c, seed_key(seed))
    tac = with_device_cap(TriAccelConfig(**tf["triaccel"]), devices[0])
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        params)
    rungs = rung_ladder(task, tf, tac, like)
    tcfg = TrainerConfig(seed=derived_seed(seed), seq_len=int(
        tf.get("seq_len", 1)), rungs=rungs, start_rung=None, **tf["trainer"])
    task = seeded_task(task, params, aux)
    del params
    tr = Trainer(task, tac, tcfg, mesh=make_dev_mesh(list(devices)))
    task.release()
    if force_codes is not None:
        _force_codes(tr, force_codes)
    return tr


def _force_codes(tr, code: int):
    """Pin every precision code to ``code`` before the first step (the
    control runs the program's own lower tier), re-seeding the compute
    copy the first forward reads."""
    from repro.train.train_step import init_compute, pack_state
    st = tr._save_state()
    ctl = st.control._replace(codes=jnp.full_like(st.control.codes, code))
    comp = init_compute(tr.task, st.params, tr.grouping, ctl, tr.tac)
    st = st._replace(control=ctl, compute=comp)
    tr.state = tr._place_resident(pack_state(tr.view, st,
                                             tr.task.compute_dtype))


def first_steps(tr, log_every: int) -> dict:
    """Drive the first ``CHECK_STEPS`` steps through ``Trainer.run`` and
    read what the reference is compared on: each step's loss, the first
    gradient as the optimizer holds it after one step, the change of the
    weights after the last, and the rows each step was fed."""
    p0 = tr.params_tree()
    tr.tcfg.log_every = 1
    tr.run(1)
    mu = tr.view.unpack(tr.state.opt_state["mu"], like=tr._params_like)
    grad_norms = reference.leaf_norms(mu)
    del mu
    tr.run(CHECK_STEPS - 1)
    delta = reference.diff_norms(tr.params_tree(), p0)
    del p0
    tr.tcfg.log_every = log_every
    log_ = tr.metrics_log[-CHECK_STEPS:]
    batches = [jax.device_get(tr._batch_for_rung(m["rung"], m["step"]))
               for m in log_]
    return {"losses": [m["loss"] for m in log_], "grad_norms": grad_norms,
            "delta_norms": delta, "batches": batches,
            "rungs": [m["rung"] for m in log_]}


def reference_numbers(cell, seed, batches) -> dict:
    mod, c = cell.config_module, cell.config
    params, aux = mod.init_weights(c, seed_key(seed))
    opt = dict(cell.traffic["trainer"])
    opt["momentum"] = opt.get("momentum", 0.9)

    def vg(p, a, b):
        return mod.ref_value_and_grad(c, p, a, b)
    return reference.sgdm_steps(vg, params, aux,
                                [jax.device_put(b) for b in batches], opt)


def control_codes(cell):
    """The precision code the control pins the program to: the
    configuration's control is the program's own lower tier."""
    return cell.config["control"]["codes"]


def code_rule(var_ema, lam, tac: dict) -> np.ndarray:
    """The per-layer precision code the configuration's threshold rule
    gives: the low tier under ``tau_low``, float32 at ``tau_high`` or
    above, bfloat16 between; float32 where the curvature passes
    ``tau_curv``."""
    v = np.asarray(var_ema, np.float32)
    codes = np.where(v < np.float32(tac["tau_low"]), 0,
                     np.where(v < np.float32(tac["tau_high"]), 1, 2))
    lam = np.asarray(lam, np.float32)
    return np.maximum(codes, np.where(lam > np.float32(tac["tau_curv"]), 2, 0))


class GcPauses:
    """Python's garbage-collection pauses between ``start`` and ``stop``."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def start(self):
        self.pauses = []
        gc.callbacks.append(self._cb)

    def stop(self):
        gc.callbacks.remove(self._cb)
        return self.pauses


def _rung_per_step(tr, s0: int, n: int, r0: int, h0: int) -> list:
    """Rung of each step in [s0, s0 + n): the scaler moves only at ticks,
    which it records as (step, new rung)."""
    ticks = {s: r for s, r, _ in tr.scaler.history[h0:]}
    out, r = [], r0
    for s in range(s0, s0 + n):
        out.append(r)
        if s in ticks:
            r = ticks[s]
    return out


def run(cell, seed, seconds, trace, devices, out_dir, t0, log,
        device_info, control=False):
    """One run of a training cell. With ``control`` the configuration's
    control stands in for the program: its own lower tier switched on."""
    tf = cell.traffic
    compiles = CompileCounter()
    pauses = GcPauses()
    tr = build(cell, seed, devices,
               control_codes(cell) if control else None)
    warmed = warm_reachable(tr, log)
    if devices[0].platform == "tpu":    # elsewhere the kernels interpret
        for key, exe in tr._executables.items():
            missing = set(cell.config["train_kernels"]) - kernels_in(exe)
            if missing:
                raise RuntimeError(f"rung {key[0]} step lacks kernels "
                                   f"{missing}")
        log(f"kernels {sorted(cell.config['train_kernels'])} in every step "
            f"executable")
    prog = first_steps(tr, int(tf["trainer"]["log_every"]))
    log(f"first steps: losses {prog['losses']} at rungs {prog['rungs']}")
    if tr.tac.enable_curvature:
        jax.block_until_ready(tr._curvature(int(tr.state.control.step)))
    t_ctrl, t_curv = tr.tac.t_ctrl, tr.tac.t_curv
    # a steady heap: what set-up allocated is collected and kept out of
    # the collector's later passes
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    log(f"setup_s {setup_s:.3f}")

    steps0 = int(tr.state.control.step)
    exe0 = tr.compile_count
    ctl = tr.state.control
    codes_in_effect = [np.asarray(jax.device_get(ctl.codes))]
    ticks, chunk_s, lens = [], [], []
    rungs, trace_dir = [], os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    prof = (jax.profiler.trace(trace_dir) if trace
            else contextlib.nullcontext())
    compiles.start()
    pauses.start()
    with prof:
        with jax.profiler.TraceAnnotation(bench_trace.WINDOW_SPAN):
            w0 = time.perf_counter()
            while True:
                c0 = time.perf_counter()
                s0, r0, h0 = (int(tr.state.control.step), tr.scaler.microbatch,
                              len(tr.scaler.history))
                n = t_ctrl - s0 % t_ctrl
                with jax.profiler.TraceAnnotation("bench.run"):
                    tr.run(n)
                with jax.profiler.TraceAnnotation("bench.sync"):
                    jax.block_until_ready(tr.state)
                    ctl = tr.state.control
                    ticks.append(jax.device_get((ctl.codes, ctl.var_ema,
                                                 ctl.lam)))
                rungs += _rung_per_step(tr, s0, n, r0, h0)
                lens.append(n)
                chunk_s.append(time.perf_counter() - c0)
                if time.perf_counter() - w0 >= seconds:
                    break
            window_s = time.perf_counter() - w0
    gc_pauses = pauses.stop()
    gc.unfreeze()
    compiled, fetched = compiles.stop()
    steps1 = steps0 + len(rungs)
    window_steps = range(steps0, steps1)
    code_refreshes = sum((s + 1) % t_ctrl == 0 for s in window_steps)
    curv_refreshes = (sum(s > 0 and s % t_curv == 0 for s in window_steps)
                      if tr.tac.enable_curvature else 0)
    switches = sum(a != b for a, b in zip(rungs, rungs[1:]))
    samples = sum(rungs)
    log(f"window {window_s:.3f}s: steps {len(rungs)} ({steps0}..{steps1 - 1}),"
        f" samples {samples}, rungs {sorted(set(rungs))}, code refreshes "
        f"{code_refreshes}, curvature refreshes {curv_refreshes}, rung "
        f"switches {switches}, compiles in window "
        f"{compiled} (programs read from the compile cache {fetched})")
    slow = int(np.argmax(chunk_s))
    log(f"chunks {len(chunk_s)}: median {np.median(chunk_s):.3f}s, slowest "
        f"#{slow} {chunk_s[slow]:.3f}s; garbage collections {len(gc_pauses)}"
        f", longest {max((p for _, p in gc_pauses), default=0.0):.4f}s")
    # the codes each chunk ran at: those in force when it began
    codes_in_effect += [np.asarray(c) for c, _, _ in ticks[:-1]]
    share = [sum(n * float(np.mean(c == k))
                 for n, c in zip(lens, codes_in_effect)) / sum(lens)
             for k in range(3)]
    misses = int(sum(np.sum(np.asarray(c) != code_rule(v, l, tf["triaccel"]))
                     for c, v, l in ticks))
    log(f"window precision: fp8 {100 * share[0]:.2f}%, bf16 "
        f"{100 * share[1]:.2f}%, fp32 {100 * share[2]:.2f}% of layer-steps; "
        f"codes chosen at {len(ticks)} ticks, {misses} layer codes off the "
        f"threshold rule")
    if tr.compile_count != exe0 or compiled:
        raise RuntimeError(f"{compiled} programs ({tr.compile_count - exe0} "
                           f"step executables) compiled inside the window "
                           f"(warmed rungs {warmed})")
    losses = [m["loss"] for m in tr.metrics_log if m["step"] >= steps0]
    failed = sum(not np.isfinite(x) for x in losses)
    flops_ps = cell.config_module.train_flops_per_sample(cell.config, tf)
    dev = device_info(devices)
    stats = devices[0].memory_stats() or {}
    context = {
        "kind": "train", "window_s": window_s, "samples": samples,
        "steps": len(rungs), "rungs": rungs, "flops_per_sample": flops_ps,
        "config": cell.config, "traffic": tf, "peak_kind": dev["kind"],
        "param_count": sum(int(x.size)
                           for x in jax.tree.leaves(tr._params_like)),
        "grad_bytes": jnp.dtype(tr.task.compute_dtype).itemsize,
        "step_bytes": max((v for (r, _), v in tr.measured_bytes.items()
                           if r in set(rungs)), default=0.0),
        "bytes_limit": int(stats.get("bytes_limit", 0)),
        "chips": len(devices),
    }
    del tr
    gc.collect()
    result = {"metrics": {tf["rate_metric"]: samples / window_s,
                          "setup_s": setup_s},
        "attempted": len(rungs), "failed": failed, "device": dev}
    if trace:
        red = bench_trace.reduce_dir(trace_dir)
        context["trace"] = red
        result["device"] = dict(dev, busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = red.breakdown()
    result["context"] = context
    ref = reference_numbers(cell, seed, prog["batches"])
    result["checks"] = reference.compare(prog, ref, cell.limits,
                                         code_rule_misses=misses)
    for c in result["checks"]:
        log(f"{c['name']} {c['value']:.6g} at {c['where'] or '-'} "
            f"(limit {c['limit']})")
    return result
