"""Host-side training engine: Tri-Accel control cadence, elastic batch
rungs, fault tolerance (atomic async checkpoints, preemption, resume,
elastic re-shard), and deterministic restartable data — for ANY TrainTask
(LM, enc-dec, vision) through one code path.

Straggler/failure model (see DESIGN.md §3): data is a pure function of
(seed, step, host), so any restart — same or different mesh size — resumes
bit-identically from the last committed checkpoint without replaying or
skipping batches; there is no data-loader state to rebuild. Preemption
(SIGTERM) triggers checkpoint-and-exit. Batch-rung changes swap between
AOT-compiled executables (zero-stall actuation of §3.3): ``warm_rungs()``
lowers + compiles the step for every configured rung ahead of time, keyed
on (rung, state treedef), so the first step on any rung never stalls on
XLA.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.checkpoint import (AsyncCheckpointer, latest_step,
                                         manifest_keys, restore_checkpoint)
from repro.core import curvature as curv
from repro.core.batch_scaler import BatchScaler, with_device_cap
from repro.core.controller import init_control, with_curvature
from repro.core.precision import TriAccelConfig, check_ladder_kernels
from repro.launch.mesh import make_dev_mesh
from repro.launch import sharding as shd
from repro.nn.module import split_params
from repro.optim.optimizers import adamw, sgdm
from repro.resilience.faults import (FaultPlan, corrupt_checkpoint,
                                     is_oom_error, simulated_oom)
from repro.resilience.recovery import (DivergenceError, DivergenceWatchdog,
                                       RecoveryConfig)
from repro.train.schedules import warmup_cosine
from repro.train.task import TrainTask, task_for_config
from repro.train.train_step import (TrainState, init_compute,
                                    make_train_step, pack_state,
                                    resolve_fused, unpack_state)


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 200
    base_lr: float = 3e-3
    warmup_steps: int = 20
    optimizer: str = "sgdm"
    momentum: float = 0.9
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    accum: int = 1
    seed: int = 0
    seq_len: int = 128
    rungs: tuple = (8,)
    start_rung: Optional[int] = None  # None: largest rung that fits
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    log_every: int = 10
    b_curv: int = 4
    elastic_true_batch: bool = True   # paper mode: rung changes global B
    #: fused Pallas update phase (DESIGN.md §9); None = auto (on whenever
    #: the optimizer carries a kernel spec), False = jnp reference oracle
    fused_update: Optional[bool] = None
    #: recovery supervision (DESIGN.md §13): OOM retry budget, divergence
    #: watchdog, rollback demotions
    recovery: RecoveryConfig = dataclasses.field(
        default_factory=RecoveryConfig)


class Trainer:
    """The single Tri-Accel engine. Accepts a ``TrainTask`` (or a bare
    model config, wrapped via ``task_for_config``)."""

    def __init__(self, task, tac: TriAccelConfig, tcfg: TrainerConfig,
                 mesh=None, fault_plan: Optional[FaultPlan] = None):
        with obs.span("train.init"):
            self._init(task, tac, tcfg, mesh, fault_plan)

    def _init(self, task, tac, tcfg, mesh, fault_plan):
        """Init, placement, slab pack, memory model and data stream."""
        if not isinstance(task, TrainTask):
            task = task_for_config(task)
        self.task = task
        self.cfg = task.cfg
        self.tcfg = tcfg
        self.mesh = mesh if mesh is not None else make_dev_mesh()
        device = self.mesh.devices.flat[0]
        self.tac = tac = with_device_cap(tac, device)
        key = jax.random.PRNGKey(tcfg.seed)

        wrapped, aux_state = task.init(key)
        params, axes = split_params(wrapped)
        self.param_axes = axes
        self.param_sh = shd.param_shardings(
            axes, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                               params), self.mesh)
        params = jax.device_put(params, self.param_sh)
        aux_state = jax.device_put(aux_state, shd.replicated(self.mesh))

        self.grouping = task.grouping(params)
        opt = (sgdm(tcfg.momentum, tcfg.weight_decay) if tcfg.optimizer == "sgdm"
               else adamw(weight_decay=tcfg.weight_decay))
        self.opt = opt
        schedule = warmup_cosine(tcfg.base_lr, tcfg.warmup_steps,
                                 tcfg.total_steps)
        self.fused = (tcfg.fused_update if tcfg.fused_update is not None
                      else resolve_fused(opt, tac))
        if self.fused:
            check_ladder_kernels(tac.ladder, device.platform)
        # slab residency (DESIGN.md §10): master/moments/compute live as
        # (rows, 512) slabs ACROSS steps whenever the step is fused — pack
        # runs once here (and on restore), unpack only at checkpoint/eval/
        # export boundaries. Needs an all-floating params tree.
        self._params_like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        self.resident = self.fused and all(
            jnp.issubdtype(l.dtype, jnp.floating)
            for l in jax.tree.leaves(params))
        self.slab_shards = self._dp_size() if self.resident else 1
        if self.resident:
            from repro.kernels.layout import slab_view
            self.view = slab_view(params, self.grouping,
                                  shards=self.slab_shards)
        self._step_fn = make_train_step(
            task, tac, opt, self.grouping, schedule, accum=tcfg.accum,
            grad_clip=tcfg.grad_clip, fused_update=self.fused,
            resident_params=self._params_like if self.resident else None,
            slab_shards=self.slab_shards, slab_mesh=self.mesh)
        control = init_control(self.grouping.num_layers, tac)
        compute = ()
        if self.fused:
            compute = init_compute(task, params, self.grouping, control, tac)
        state = TrainState(params, aux_state, opt.init(params),
                           control, compute)
        if self.resident:
            state = self._place_resident(
                pack_state(self.view, state, task.compute_dtype))
        elif self.fused:
            state = state._replace(compute={
                "tree": jax.device_put(compute["tree"], self.param_sh),
                "p_amax": jax.device_put(compute["p_amax"],
                                         shd.replicated(self.mesh))})
        self.state = state

        # §3.3: memory model + rung controller (task-provided HBM model)
        mm = task.memory_model(params, opt_slots=opt.slots,
                               mesh_size=self.mesh.size)
        self.scaler = BatchScaler(tcfg.rungs,
                                  task.tokens_per_sample(tcfg.seq_len), mm,
                                  tac, start_rung=tcfg.start_rung)

        self.stream = task.data_stream(self._global_batch(), seed=tcfg.seed,
                                       seq_len=tcfg.seq_len)
        # AOT executable cache: (rung, state treedef) -> jax.stages.Compiled
        self._executables: Dict[Tuple[int, Any], Any] = {}
        # measured memory_analysis() bytes per executable, same keys as the
        # AOT cache (max over hosts); feeds the §3.3 controller's overlay
        self.measured_bytes: Dict[Tuple[int, Any], float] = {}
        self.compile_count = 0
        self.ckpt = (AsyncCheckpointer(tcfg.ckpt_dir, tcfg.ckpt_keep)
                     if tcfg.ckpt_dir else None)
        self._preempted = False
        self.metrics_log = []
        # --- recovery supervision (DESIGN.md §13) -------------------------
        self.fault_plan = fault_plan
        self._watchdog = (DivergenceWatchdog(tcfg.recovery)
                          if tcfg.recovery.watchdog else None)
        self.oom_events: list = []       # (step, rung) per caught OOM
        self.rollback_events: list = []  # (diverged_step, restored_step)

    # ------------------------------------------------------------- utils --
    def _global_batch(self) -> int:
        return self.scaler.microbatch * self._dp_size()

    def _dp_size(self) -> int:
        dp = 1
        for a in ("pod", "data"):
            if a in self.mesh.axis_names:
                dp *= self.mesh.shape[a]
        return dp

    @staticmethod
    def _abstract(x) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))

    # ------------------------------------------------- slab residency -----
    def _place_resident(self, state: TrainState) -> TrainState:
        """Lay a slab-form state onto the mesh: slabs row-range sharded
        over the fsdp axes (launch.sharding.slab_sharding), everything
        else replicated."""
        slab = shd.slab_sharding(self.mesh, self.slab_shards)
        rep = shd.replicated(self.mesh)
        opt2 = {k: jax.device_put(v, slab if k in ("mu", "m", "v") else rep)
                for k, v in state.opt_state.items()}
        compute = {"slab": jax.device_put(state.compute["slab"], slab),
                   "p_amax": jax.device_put(state.compute["p_amax"], rep)}
        return TrainState(jax.device_put(state.params, slab),
                          jax.device_put(state.aux_state, rep),
                          opt2, jax.device_put(state.control, rep), compute)

    def params_tree(self):
        """fp32 master params in TREE form — the eval/export boundary view.
        On the resident path this is the one sanctioned per-call unpack;
        inside the step the masters never leave slab form."""
        if not self.resident:
            return self.state.params
        return self.view.unpack(self.state.params, like=self._params_like)

    def _save_state(self) -> TrainState:
        """Checkpoint boundary: resident slabs unpack to TREE form on save,
        so checkpoints stay mesh- and residency-agnostic (pre-residency
        readers parse them unchanged)."""
        if not self.resident:
            return self.state
        return unpack_state(self.view, self.state, self._params_like)

    def _tree_template(self) -> TrainState:
        """Abstract tree-form state matching what ``_save_state`` writes —
        the restore template for resident trainers."""
        opt_sds = jax.eval_shape(self.opt.init, self._params_like)
        comp_sds = jax.eval_shape(
            lambda p, c: init_compute(self.task, p, self.grouping, c,
                                      self.tac),
            self._params_like, self.state.control)
        return TrainState(self._params_like, self.state.aux_state, opt_sds,
                          self.state.control, comp_sds)

    def _get_step(self, rung: int):
        """AOT-compiled executable per batch rung (zero-stall rung switches).

        The cache key includes the state treedef, so a structural change
        (e.g. restoring a checkpoint with different aux state) can never
        dispatch into a stale executable."""
        key = (rung, jax.tree_util.tree_structure(self.state))
        exe = self._executables.get(key)
        if exe is None:
            with obs.span("train.compile", rung=rung) as sp:
                try:
                    state_sds = jax.tree.map(self._abstract, self.state)
                    batch_sds = jax.tree.map(self._abstract,
                                             self._batch_for_rung(rung, 0))
                    with self.mesh, shd.activation_mesh(self.mesh):
                        exe = (jax.jit(self._step_fn, donate_argnums=(0,))
                               .lower(state_sds, batch_sds).compile())
                except Exception as e:      # noqa: BLE001 — re-raised
                    if is_oom_error(e):
                        sp.outcome = "oom"
                    raise
                self._executables[key] = exe
                self.compile_count += 1
                self._harvest_measured(key, exe)
        return exe

    def _harvest_measured(self, key, exe):
        """Record the executable's measured per-host footprint (max over
        hosts) into the trainer table and the controller's rung overlay."""
        mb = shd.harvested_exe_bytes(exe)
        if mb is None:
            return
        rung = key[0]
        self.measured_bytes[key] = mb
        self.scaler.model.record_measured(
            rung, mb, rung * self.scaler.seq_len, ladder=self.tac.ladder)

    def reharvest_measured(self):
        """Re-read memory_analysis() for every cached executable — after an
        elastic re-shard restore the (rung, treedef) keys survive but the
        per-host footprint (and the most-loaded host) can change."""
        for key, exe in self._executables.items():
            self._harvest_measured(key, exe)

    def _rung_measured(self, rung: int) -> Optional[float]:
        """Harvested bytes for ``rung`` at the LIVE state treedef (None until
        the rung's executable exists — analytic fallback in the scaler)."""
        key = (rung, jax.tree_util.tree_structure(self.state))
        return self.measured_bytes.get(key)

    def serving_amax_tree(self):
        """Per-leaf absmax of the live master weights, derived from the
        fused path's carried per-layer table — hand to
        ``ServeEngine(amax_tree=...)`` so the serving precision ladder's
        fp8 cast (kernels.qdq_cast) skips its amax reduction phase. None
        on the reference path (the cast then reduces its own amax)."""
        if not self.fused:
            return None
        if self.resident:
            return self.view.amax_tree(self.state.compute["p_amax"],
                                       self._params_like)
        from repro.kernels.layout import slab_view
        view = slab_view(self.state.params, self.grouping)
        return view.amax_tree(self.state.compute["p_amax"], self.state.params)

    def warm_rungs(self):
        """Pre-compile the train step for every configured rung; afterwards
        a step on any rung triggers zero new XLA compilations, and the
        measured table holds every rung's real footprint."""
        for r in self.tcfg.rungs:
            self._get_step(r)

    def _batch_for_rung(self, rung: int, step: int):
        with obs.span("train.data", rung=rung, step=step):
            stream = dataclasses.replace(
                self.stream, global_batch=self._dp_size() * rung) \
                if self.tcfg.elastic_true_batch else self.stream
            return self._place_batch(stream.batch(step))

    def _place_batch(self, batch):
        """Lay a host-built global batch over the mesh's data axes (the
        step executables are compiled for exactly this placement)."""
        return jax.device_put(batch, shd.batch_shardings(batch, self.mesh))

    # ------------------------------------------------- fault tolerance ----
    def install_preemption_handler(self):
        """Checkpoint-and-exit on SIGTERM (spot reclamation) AND SIGINT
        (Ctrl-C). Prior handlers are CHAINED, not clobbered — a launcher's
        own SIGTERM hook (metrics flush, lease release) still runs."""
        def _make(prev):
            # SIG_DFL/SIG_IGN aren't callable; Python's default SIGINT
            # handler raises KeyboardInterrupt, which would defeat the
            # graceful checkpoint-and-exit — chain real handlers only
            chain = prev if (callable(prev)
                             and prev is not signal.default_int_handler) \
                else None

            def _handler(signum, frame):
                self._preempted = True
                if chain is not None:
                    chain(signum, frame)
            return _handler

        for sig in (signal.SIGTERM, signal.SIGINT):
            prev = signal.getsignal(sig)
            signal.signal(sig, _make(prev))

    @staticmethod
    def _fill_missing():
        """Schema-evolution fills for leaves newer than the checkpoint on
        disk (repro.checkpoint fill_missing contract): checkpoints written
        before the rollback demotion existed restore at the neutral 1.0."""
        return {"lr_demote": np.ones((), np.float32)}

    def maybe_restore(self) -> int:
        if not (self.tcfg.ckpt_dir and latest_step(self.tcfg.ckpt_dir) is not None):
            return 0
        if self.resident:
            return self._restore_resident()
        # elastic re-shard: checkpoints are host-layout, so leaves re-place
        # onto THIS mesh whatever mesh wrote them. Each leaf lands on the
        # LIVE state's sharding, so AOT executables warmed before the
        # restore stay dispatchable.
        try:
            host = restore_checkpoint(self.tcfg.ckpt_dir, self.state,
                                      fill_missing=self._fill_missing())
            self.state = jax.tree.map(
                lambda h, cur: jax.device_put(h, cur.sharding), host,
                self.state)
        except KeyError:
            if not self.fused:
                raise
            # checkpoint written before the fused carry existed (or by a
            # reference-path run): restore the 4-field state and re-seed
            # TrainState.compute from the restored masters
            base = self.state._replace(compute=())
            host = restore_checkpoint(self.tcfg.ckpt_dir, base,
                                      fill_missing=self._fill_missing())
            new = jax.tree.map(
                lambda h, cur: jax.device_put(h, cur.sharding), host, base)
            compute = init_compute(self.task, new.params, self.grouping,
                                   new.control, self.tac)
            compute = {
                "tree": jax.device_put(compute["tree"], self.param_sh),
                "p_amax": jax.device_put(compute["p_amax"],
                                         shd.replicated(self.mesh))}
            self.state = new._replace(compute=compute)
        self.reharvest_measured()
        return int(self.state.control.step)

    def _restore_resident(self) -> int:
        """Restore a TREE-form checkpoint into the slab-resident trainer:
        leaves load host-layout, pack into slabs, and re-place onto THIS
        mesh's row-range partition — an elastic re-shard re-partitions the
        slab directly instead of resurrecting a compiler-chosen layout.
        Handles every on-disk generation: 5-field tree states (what
        ``_save_state`` writes, and what pre-residency fused runs wrote)
        and 4-field pre-fused states (compute re-seeded from the restored
        masters)."""
        keys = manifest_keys(self.tcfg.ckpt_dir)
        has_compute = any(k.startswith(".compute") for k in keys)
        tmpl = self._tree_template()
        if not has_compute:
            tmpl = tmpl._replace(compute=())
        host = restore_checkpoint(self.tcfg.ckpt_dir, tmpl,
                                  fill_missing=self._fill_missing())
        if not has_compute:
            host = host._replace(compute=init_compute(
                self.task, host.params, self.grouping, host.control,
                self.tac))
        self.state = self._place_resident(
            pack_state(self.view, host, self.task.compute_dtype))
        self.reharvest_measured()
        return int(self.state.control.step)

    # -------------------------------------------------------------- run ---
    def run(self, steps: Optional[int] = None):
        steps = steps if steps is not None else self.tcfg.total_steps
        start = int(self.state.control.step)
        end = start + steps
        with obs.span("train.run", start=start, steps=steps):
            t0 = time.time()
            step = start
            while step < end:
                with obs.step_span("train.step", step,
                                   rung=self.scaler.microbatch):
                    step = self._run_step(step, t0)
            if self.ckpt:
                self.ckpt.save(end, self._save_state(), block=True)
                self._maybe_corrupt(end)
        return self.metrics_log

    def _run_step(self, step: int, t0: float) -> int:
        """One iteration of ``run``: the step and the host cadences due
        after it. Returns the next step (a rollback's restored step)."""
        if self.fault_plan is not None and \
                self.fault_plan.fires("train.sigterm", step):
            self._deliver_sigterm()
        if self._preempted:
            if self.ckpt:
                self.ckpt.save(step, self._save_state(), block=True)
                self._maybe_corrupt(step)
            raise SystemExit(143)
        if self.fault_plan is not None:
            self._inject_nonfinite(step)
        self.state, metrics, rung = self._dispatch(step)

        # §3.2 curvature cadence (host side, tiny batch)
        if self.tac.enable_curvature and step > 0 and \
                step % self.tac.t_curv == 0:
            lam = self._curvature(step)
            self.state = self.state._replace(
                control=with_curvature(self.state.control, lam))
        # §3.3 batch-rung cadence: measured-first (the harvested
        # memory_analysis() bytes of THIS rung's executable), analytic
        # fallback when the backend reported nothing
        if step > 0 and step % self.tac.t_ctrl == 0:
            with obs.span("train.control", step=step, rung=rung) as sp:
                codes = jax.device_get(self.state.control.codes)
                self.scaler.observe(step, codes=list(codes),
                                    measured_bytes=self._rung_measured(rung))
                sp.attrs["rung_after"] = self.scaler.microbatch
        # checkpoint cadence — suppressed while the watchdog has
        # suspect steps in flight: a mid-burst state (control carries
        # the overflow) must never displace the clean generation a
        # rollback needs
        if self.ckpt and step > 0 and step % self.tcfg.ckpt_every == 0 \
                and (self._watchdog is None or self._watchdog.healthy):
            self.ckpt.save(step, self._save_state())
            self._maybe_corrupt(step)
        if step % self.tcfg.log_every == 0:
            m = {k: float(v) for k, v in jax.device_get(metrics).items()}
            m.update(step=step, rung=rung,
                     mem_gb=self.scaler._mem(self.scaler.idx) / 1e9,
                     wall_s=round(time.time() - t0, 2))
            self.metrics_log.append(m)
        if self._watchdog is not None:
            host = jax.device_get({"loss": metrics.get("loss", 0.0),
                                   "finite": metrics.get("grads_finite",
                                                         True)})
            if self._watchdog.observe(float(host["loss"]),
                                      bool(host["finite"])):
                return self._rollback(step)
        return step + 1

    # ------------------------------------------- recovery (DESIGN.md §13) -
    def _dispatch(self, step: int):
        """One train step with OOM-reactive recovery: a backend
        RESOURCE_EXHAUSTED poisons the rung (``BatchScaler.mark_oom``),
        steps down, and re-dispatches the SAME batch — bit-identical by
        construction, the batch is a pure function of (seed, step, host) —
        into the already-warmed smaller executable (zero new compiles).
        Bounded by ``recovery.max_oom_retries``; exhaustion (or an OOM on
        the smallest rung) escalates to checkpoint-and-exit by re-raising
        after a blocking save."""
        rec = self.tcfg.recovery
        err: Optional[BaseException] = None
        for _ in range(rec.max_oom_retries + 1):
            rung = self.scaler.microbatch
            try:
                if self.fault_plan is not None and self.fault_plan.fires(
                        "train.step_oom", step, rung=rung):
                    raise simulated_oom("train.step_oom", step, rung)
                step_fn = self._get_step(rung)
                batch = self._batch_for_rung(rung, step)
                state, metrics = step_fn(self.state, batch)
                return state, metrics, rung
            except Exception as e:          # noqa: BLE001 — filtered below
                if not is_oom_error(e):
                    raise
                err = e
                self.oom_events.append((step, rung))
                if not self._state_alive():
                    # a REAL dispatch OOM can consume the donated state
                    # buffers — nothing host-side to retry with; the
                    # process must restart from the last checkpoint
                    raise
                if self.scaler.mark_oom(rung) == rung:
                    break                   # smallest rung OOM'd: escalate
        if self.ckpt and self._state_alive():
            self.ckpt.save(step, self._save_state(), block=True)
        raise err if err is not None else RuntimeError("unreachable")

    def _state_alive(self) -> bool:
        """False when any live-state buffer was consumed (donated) by a
        failed dispatch — retry needs intact inputs."""
        return all(not getattr(l, "is_deleted", lambda: False)()
                   for l in jax.tree.leaves(self.state))

    def _rollback(self, step: int) -> int:
        """Divergence rollback: restore the last committed checkpoint and
        apply the deterministic demotion — loss scale down (gpu ladder
        floors at 1.0) and ``ControlState.lr_demote`` down — so the replay
        is NOT a bit-identical rerun into the same blow-up. Returns the
        restored step (the loop resumes there); bounded by
        ``recovery.max_rollbacks``."""
        rec = self.tcfg.recovery
        if self.ckpt:
            self.ckpt.wait()    # never race an in-flight save
        if not (self.tcfg.ckpt_dir
                and latest_step(self.tcfg.ckpt_dir) is not None):
            raise DivergenceError(
                f"diverged at step {step} with no committed checkpoint "
                f"to roll back to")
        if len(self.rollback_events) >= rec.max_rollbacks:
            raise DivergenceError(
                f"diverged at step {step}: rollback budget "
                f"({rec.max_rollbacks}) exhausted")
        restored = self.maybe_restore()
        ctrl = self.state.control
        ls = ctrl.loss_scale * rec.loss_scale_demotion
        if self.tac.ladder == "gpu":
            ls = jnp.maximum(ls, 1.0)
        new_ls = jax.device_put(ls.astype(jnp.float32),
                                ctrl.loss_scale.sharding)
        new_demote = jax.device_put(
            (ctrl.lr_demote * rec.lr_demotion).astype(jnp.float32),
            ctrl.lr_demote.sharding)
        self.state = self.state._replace(control=ctrl._replace(
            loss_scale=new_ls, lr_demote=new_demote))
        self._watchdog.reset()
        self.rollback_events.append((step, restored))
        return restored

    def _inject_nonfinite(self, step: int):
        """train.nonfinite fault: force the carried loss scale to inf so
        this step's grads overflow through the REAL finite-gate path (the
        update is skipped in-graph, grads_finite=0 lands in metrics). The
        poisoned scale persists in the carry — recovery is the watchdog's
        rollback, exactly as for an organic divergence."""
        if self.fault_plan.fires("train.nonfinite", step) is None:
            return
        ctrl = self.state.control
        bad = jax.device_put(jnp.asarray(jnp.inf, jnp.float32),
                             ctrl.loss_scale.sharding)
        self.state = self.state._replace(
            control=ctrl._replace(loss_scale=bad))

    def _deliver_sigterm(self):
        """train.sigterm fault: deliver a REAL signal through the process
        so the chained preemption handlers run, then wait for the flag
        (CPython runs handlers at the next bytecode boundary)."""
        signal.raise_signal(signal.SIGTERM)
        for _ in range(1000):
            if self._preempted:
                return
            time.sleep(0.001)
        self._preempted = True    # handler not installed: honor the fault

    def _maybe_corrupt(self, step: int):
        """ckpt.corrupt fault: damage the generation just committed (waits
        out the async writer first — the fault models storage tearing a
        COMPLETED commit, which is exactly what CRC verification + restore
        fallback must survive)."""
        if self.fault_plan is None:
            return
        f = self.fault_plan.fires("ckpt.corrupt", step)
        if f is None:
            return
        self.ckpt.wait()
        corrupt_checkpoint(self.tcfg.ckpt_dir, f.kind, self.fault_plan.rng)

    def _curvature(self, step: int):
        with obs.span("train.curvature", step=step):
            mb = self._place_batch(self.stream.batch(step))
            small = jax.tree.map(lambda x: x[:self.tcfg.b_curv], mb)
            aux = self.state.aux_state
            params = self.params_tree()          # eval boundary: one unpack
            loss_fn = lambda p, b: self.task.curvature_loss(p, aux, b)
            if self.tac.curvature_method == "fisher":
                g = jax.grad(loss_fn)(params, small)
                return curv.fisher_layer(g, self.grouping.mean)
            key = jax.random.PRNGKey(step)
            return curv.hutchinson_layer_traces(
                loss_fn, params, lambda t: self.grouping.mean(t),
                key, 1, small)
