"""ServeSession: elastic continuous-batching serving loop over any
ServableTask (LM, enc-dec, or the vision testbed).

One session owns an admission queue (FIFO, or the SLO scheduler —
priority classes, deadlines, aging — from repro.serve.scheduler), a slot
array at the current batch rung, the batched decode caches, and a
``ServeEngine`` of AOT-warmed executables. Each ``step()``:

  1. control cadence (every ``t_ctrl`` steps): the §3.3 BatchScaler over the
     task's ``serve_memory_model`` updates the memory-capacity rung
     MEASURED-FIRST — ``warm()`` harvests every (rung, tier) executable's
     ``memory_analysis()`` bytes into the model's overlay, so both the
     pressure signal and the climb guard run on real footprints — the
     latency ceiling is refreshed from the measured per-step latency table
     (the largest rung whose modeled p99 step time fits the tightest SLO
     class budget — DESIGN.md §11), and, when ``auto_tier``, the
     decode-weight precision tier is re-picked: the highest-precision
     configured tier whose (measured-first) footprint fits under
     rho_high * cap;
  2. rung resize: grow/shrink to the smallest configured rung covering the
     load (never evicting in-flight requests), capped by BOTH the memory
     and latency controllers, repacking cache rows through a pre-compiled
     gather — in-flight outputs are bit-identical across the transition
     (tests/test_serve.py);
  3. admission: queued requests fill free slots in scheduler order. Whole-
     prompt admission scatters one compiled prefill into the slot's cache
     rows (ring-aware); with ``prefill_chunk`` set, the prompt is instead
     consumed in fixed-size chunks — ONE chunk per request per step,
     teacher-forced through the decode hook against the slot's own rows —
     so a long prompt never stalls the in-flight decodes (step 4 still runs
     every step while the chunks land);
  4. one decode step for EVERY active slot, each at its own position
     (token-level continuous batching: the decode index is a (B,) vector;
     empty and still-prefilling rows are masked to exact cache no-ops).
     The step's wall time feeds the (rung, tier) latency table.

Cache-free tasks (vision) skip 3–4 and serve whole requests per step
through the batched ``infer`` executable at the same rung/tier rails.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch_scaler import BatchScaler, with_device_cap
from repro.core.precision import TriAccelConfig, check_ladder_kernels
from repro.nn.module import split_params
from repro.serve.batching import Request, RequestQueue, pick_rung
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import LatencyTable, Scheduler, SchedulerConfig
from repro.resilience.faults import FaultPlan, is_oom_error, simulated_oom
from repro.train.serve import as_task


def _pct(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


@dataclasses.dataclass
class ServeConfig:
    prompt_len: int = 16              # fixed prompt length (whole-prompt mode)
    total_len: int = 48               # cache horizon: prompt + generation
    rungs: Tuple[int, ...] = (2, 4)   # batch rung ladder (ascending)
    tiers: Tuple[int, ...] = (1,)     # decode-weight precision tiers warmed
    ladder: str = "tpu"               # fp8 (tpu) vs fp16 (gpu) low tier
    cache_dtype: Any = jnp.bfloat16
    max_new_tokens: int = 16          # per-request default
    t_ctrl: int = 8                   # §3.4 control cadence, in decode steps
    mem_cap_bytes: Optional[float] = None   # None: the device's own limit
    auto_tier: bool = True
    seed: int = 0
    # --- SLO scheduling (DESIGN.md §11) ---------------------------------
    # chunked prefill: prompt tokens consumed per admission step; None =
    # whole-prompt admission with the fixed prompt_len (the PR-2 behavior).
    # With a chunk size set, prompts are VARIABLE length (1..total_len-1).
    prefill_chunk: Optional[int] = None
    schedule: str = "fifo"            # "fifo" | "slo" admission policy
    aging_steps: int = 64             # SLO scheduler: starvation-freedom aging
    on_infeasible: str = "reject"     # SLO scheduler: "reject" | "degrade"
    # per-priority-class p99 DECODE-STEP budget (ms); the latency ceiling
    # stops the rung climbing past the tightest budget of any class present
    latency_slo_ms: Optional[Dict[int, float]] = None
    # --- recovery (DESIGN.md §13) ---------------------------------------
    # OOM-recovery evictions per request before it is failed instead of
    # requeued — a bounded retry turns a crashed session into per-request
    # status="failed"
    max_request_retries: int = 2


class ServeSession:
    """Task-level serving session (the API every arch in
    ``repro.models.registry.list_tasks()`` serves through)."""

    def __init__(self, task, cfg: Optional[ServeConfig] = None, params=None,
                 aux_state=None, tac: Optional[TriAccelConfig] = None,
                 fault_plan: Optional[FaultPlan] = None):
        self.task = as_task(task)
        cfg = cfg if cfg is not None else ServeConfig()
        self.cfg = cfg
        if params is None:
            wrapped, aux_state = self.task.init(jax.random.PRNGKey(cfg.seed))
            params, _ = split_params(wrapped)
        device = jax.devices()[0]
        self.tac = with_device_cap(tac if tac is not None else TriAccelConfig(
            ladder=cfg.ladder, mem_cap_bytes=cfg.mem_cap_bytes,
            t_ctrl=cfg.t_ctrl), device)
        tiers = tuple(sorted(set(cfg.tiers)))
        if 0 in tiers:                     # tier 0 runs the qdq_cast kernel
            check_ladder_kernels(cfg.ladder, device.platform)
        self.tier = 1 if 1 in tiers else tiers[-1]
        self._tier_locked = not cfg.auto_tier
        self.mm = self.task.serve_memory_model(
            params, cfg.total_len, ladder=cfg.ladder, weight_tier=self.tier,
            enc_len=cfg.prompt_len)
        self.scaler = BatchScaler(list(cfg.rungs),
                                  self.task.tokens_per_sample(cfg.total_len),
                                  self.mm, self.tac)
        self.engine = ServeEngine(
            self.task, params, aux_state, total_len=cfg.total_len,
            prompt_len=cfg.prompt_len, rungs=cfg.rungs, tiers=tiers,
            ladder=cfg.ladder, cache_dtype=cfg.cache_dtype,
            prefill_chunk=cfg.prefill_chunk)
        self.chunked = self.engine.chunked
        self.rung = cfg.rungs[0]
        self.slots: List[Optional[Request]] = [None] * self.rung
        self.caches = (self.engine.init_caches(self.rung)
                       if self.task.serves_tokens else None)
        if cfg.schedule == "slo":
            self.queue: Any = Scheduler(SchedulerConfig(
                aging_steps=cfg.aging_steps,
                on_infeasible=cfg.on_infeasible))
        elif cfg.schedule == "fifo":
            self.queue = RequestQueue()
        else:
            raise ValueError(f"unknown schedule {cfg.schedule!r} "
                             f"(expected 'fifo' or 'slo')")
        self.requests: Dict[int, Request] = {}
        self.steps = 0
        self.decoded_tokens = 0
        self.lat = LatencyTable()
        self.lat_rung: Optional[int] = None   # latency ceiling (None = off)
        self.rung_history: List[Tuple[int, int]] = [(0, self.rung)]
        self.tier_history: List[Tuple[int, int]] = [(0, self.tier)]
        # --- recovery (DESIGN.md §13) -----------------------------------
        self.fault_plan = fault_plan
        self.oom_events: List[Tuple[int, int, int, str]] = []

    # ------------------------------------------------------------- public --
    @property
    def compile_count(self) -> int:
        return self.engine.compile_count

    def warm(self) -> int:
        """AOT-compile every (rung, tier) executable and harvest each one's
        measured bytes into the rung controller; returns compile count."""
        n = self.engine.warm()
        self.sync_measured()
        return n

    def sync_measured(self) -> None:
        """Refresh the engine's per-executable measured table and copy it
        into the memory model's (rung, tier) overlay — called after warm()
        and again after an elastic re-shard (the AOT keys survive, but
        per-host footprints change with the mesh, so re-read them)."""
        self.engine.reharvest_measured()
        self._refresh_overlay()

    def _refresh_overlay(self) -> None:
        """Copy the engine's measured table into the model overlay (cheap
        dict reads, no re-harvest). Run on every control tick so a session
        serving WITHOUT warm() — executables lazily compiled and harvested
        on first dispatch — still closes the loop."""
        for rung in self.engine.rungs:
            for tier in self.engine.tiers:
                # a poisoned (rung, tier) keeps its above-cap sentinel: the
                # engine's table still holds the optimistic pre-OOM harvest
                if (rung, tier) in self.mm.poisoned:
                    continue
                mb = self.engine.measured_bytes(rung, tier)
                if mb is not None:
                    self.mm.measured[(rung, tier)] = mb

    def submit(self, inputs: Dict[str, np.ndarray],
               max_new_tokens: Optional[int] = None, priority: int = 1,
               deadline_ms: Optional[float] = None) -> int:
        """Queue one request (unbatched inputs); returns its id.

        ``priority`` (0 = most urgent) and ``deadline_ms`` (completion
        deadline relative to now) drive the SLO scheduler; the FIFO queue
        carries them unused. Validation raises ``ValueError`` — these are
        load-bearing admission checks, not debug asserts (``python -O``
        must not disable them)."""
        n = max_new_tokens if max_new_tokens is not None \
            else self.cfg.max_new_tokens
        if n < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n}")
        if self.task.serves_tokens:
            tokens = inputs.get("tokens")
            if tokens is None:
                raise ValueError("token-serving request needs 'tokens'")
            p = int(np.asarray(tokens).shape[0])
            if self.chunked:
                if p < 1:
                    raise ValueError("empty prompt")
            elif p != self.cfg.prompt_len:
                raise ValueError(
                    f"prompt length {p} != configured prompt_len "
                    f"{self.cfg.prompt_len} (variable-length prompts need "
                    f"prefill_chunk set)")
            if p + n > self.cfg.total_len:
                raise ValueError(f"prompt {p} + gen {n} exceeds total_len "
                                 f"{self.cfg.total_len}")
        req = self.queue.submit(inputs, max_new_tokens=n, priority=priority,
                                deadline_ms=deadline_ms,
                                submitted_step=self.steps)
        self.requests[req.rid] = req
        return req.rid

    def set_tier(self, tier: int, lock: bool = True):
        """Manually pin the decode-weight precision tier."""
        if tier not in self.engine.tiers:
            raise ValueError(f"tier {tier} not warmed "
                             f"(configured: {self.engine.tiers})")
        if tier != self.tier:
            self.tier_history.append((self.steps, tier))
        self.tier = tier
        self._tier_locked = lock

    def step(self):
        if self.steps % self.tac.t_ctrl == 0:
            self._control()
        self._resize()
        if self.task.serves_tokens:
            self._admit()
            self._decode()
        else:
            self._infer()
        self.steps += 1

    def run(self, max_steps: int = 10_000) -> Dict[str, Any]:
        """Step until the queue drains and every request completes.

        Wall-clock accounting: ``warm_s`` is the compile time paid INSIDE
        the loop (lazily compiled executables when ``warm()`` was skipped);
        ``serve_s`` = ``wall_s`` − ``warm_s`` prices the serving itself, so
        ``tok_s`` is not understated on cold sessions. Latency aggregates
        (queue wait, time-to-first-token) cover every admitted request."""
        t0 = time.time()
        c0 = self.engine.compile_s
        while (len(self.queue) or self._active()) and self.steps < max_steps:
            self.step()
        dt = max(time.time() - t0, 1e-9)
        warm_s = self.engine.compile_s - c0
        serve_s = max(dt - warm_s, 1e-9)
        return {"steps": self.steps, "decoded_tokens": self.decoded_tokens,
                "wall_s": dt, "warm_s": warm_s, "serve_s": serve_s,
                "tok_s": self.decoded_tokens / serve_s,
                "rung_history": list(self.rung_history),
                "tier_history": list(self.tier_history),
                "compile_count": self.compile_count,
                **self.latency_report()}

    def latency_report(self) -> Dict[str, Any]:
        """Per-request latency percentiles over everything admitted so far:
        queue wait (submit → slot, in steps), time-to-first-token (wall),
        plus the rejected-request count (SLO scheduler only)."""
        reqs = list(self.requests.values())
        queue_steps = [r.admitted_step - r.submitted_step for r in reqs
                       if r.admitted_step >= 0 and r.submitted_step >= 0]
        ttft = [r.first_token_time - r.submit_time for r in reqs
                if r.first_token_step >= 0]
        return {
            "queue_steps_p50": _pct(queue_steps, 50),
            "queue_steps_p99": _pct(queue_steps, 99),
            "ttft_s_p50": _pct(ttft, 50),
            "ttft_s_p99": _pct(ttft, 99),
            "rejected": sum(r.status == "rejected" for r in reqs),
            "failed": sum(r.status == "failed" for r in reqs),
        }

    def results(self) -> Dict[int, Request]:
        return dict(self.requests)

    # ----------------------------------------------------------- internals --
    def _active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def _classes_present(self) -> List[int]:
        """Priority classes with work in the system (queued or slotted)."""
        classes = {r.priority for r in self.slots if r is not None}
        q = self.queue
        classes.update(getattr(q, "depth_by_class", dict)().keys())
        return sorted(classes)

    def _step_budget_s(self) -> Optional[float]:
        """Tightest per-step p99 budget among the classes present."""
        slo = self.cfg.latency_slo_ms
        if not slo:
            return None
        budgets = [slo[c] for c in self._classes_present() if c in slo]
        return min(budgets) / 1e3 if budgets else None

    def _control(self):
        """§3.3/§3.4 serve-side control: memory-capacity rung + latency
        ceiling + precision tier, all from measured signals. After
        ``warm()`` every (rung, tier) the controller can pick has a
        MEASURED footprint in the model's overlay, so observe()'s pressure
        signal, its climb guard, and the tier sweep below all run on
        harvested memory_analysis() bytes (analytic fallback only for
        never-compiled combinations). The latency ceiling mirrors it on the
        time axis: measured p99 step time per (rung, tier), extrapolated to
        unmeasured rungs, capped by the tightest class budget."""
        self.mm.weight_tier = self.tier
        self._refresh_overlay()
        self.lat_rung = self.lat.latency_rung(
            self.engine.rungs, self.tier, self._step_budget_s())
        # feed the harvested bytes for the controller's own (rung, tier)
        # explicitly: record_measured also re-fits the analytic calibration
        self.scaler.observe(self.steps, measured_bytes=self.mm.measured.get(
            (self.scaler.microbatch, self.tier)), rung_cap=self.lat_rung)
        if self._tier_locked or len(self.engine.tiers) < 2:
            return
        cap = self.tac.rho_high * self.tac.mem_cap_bytes
        tokens = self.rung * self.task.tokens_per_sample(self.cfg.total_len)
        usable = [t for t in sorted(self.engine.tiers, reverse=True)
                  if (self.rung, t) not in self.mm.poisoned]
        chosen = None
        for tier in usable:
            self.mm.weight_tier = tier
            if self.mm.predict(self.rung, tokens) <= cap:
                chosen = tier
                break
        if chosen is None:    # nothing fits cleanly: lowest unpoisoned tier
            chosen = usable[-1] if usable else self.tier
        self.mm.weight_tier = chosen
        if chosen != self.tier:
            self.tier = chosen
            self.tier_history.append((self.steps, chosen))

    def _resize(self):
        active = self._active()
        target = pick_rung(self.engine.rungs, len(active), len(self.queue),
                           self.scaler.microbatch, latency_rung=self.lat_rung)
        if target == self.rung:
            return
        if self.task.serves_tokens:
            src = np.zeros((target,), np.int32)
            valid = np.zeros((target,), bool)
            for j, req in enumerate(active):
                src[j], valid[j] = req.slot, True
            self.caches = self.engine.repack(self.rung, target, self.caches,
                                             src, valid)
        self.slots = list(active) + [None] * (target - len(active))
        for j, req in enumerate(active):
            req.slot = j
        self.rung = target
        self.rung_history.append((self.steps, target))

    def _finish(self, req: Request):
        req.status = "done"
        req.finished_step = self.steps
        req.finish_time = time.time()
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None

    # --------------------------------------- OOM recovery (DESIGN.md §13) --
    def _fail(self, req: Request):
        """Terminal per-request failure — the bounded-retry endpoint. The
        session keeps serving; the caller reads status='failed'."""
        req.status = "failed"
        req.finished_step = self.steps
        req.finish_time = time.time()
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None

    def _shed(self, req: Request):
        """Evict ``req`` for OOM recovery: free its slot and requeue it for
        a from-scratch admission (prefill replays; the retry is
        deterministic — same prompt, same weights). The retry budget
        (``cfg.max_request_retries``) bounds this; exhaustion fails the
        request instead of looping."""
        if req.slot is not None:
            self.slots[req.slot] = None
            req.slot = None
        self.decoded_tokens -= len(req.tokens)   # replay will re-count
        req.tokens = []
        req.index = 0
        req.prefill_pos = 0
        req.admitted_step = -1
        req.first_token_step = -1
        req.first_token_time = 0.0
        req.retries += 1
        if req.retries > self.cfg.max_request_retries:
            self._fail(req)
        else:
            self.queue.requeue(req)

    def _caches_alive(self) -> bool:
        if self.caches is None:
            return True
        return all(not getattr(l, "is_deleted", lambda: False)()
                   for l in jax.tree.leaves(self.caches))

    def _handle_oom(self, where: str):
        """Serve-side OOM recovery: poison the (rung, tier) in the measured
        overlay (never re-entered — ``BatchScaler.mark_oom``), then free
        capacity in-place: emergency step-down to the largest smaller rung
        (shedding the most recently admitted requests until the survivors
        fit, cache rows moved through the bit-exact repack gather), or —
        already at the smallest rung — demote the decode tier, or shed the
        youngest request outright. The failed dispatch is simply retried on
        the NEXT step(): positions and caches are unchanged, so the retry
        is bit-identical at the new (rung, tier)."""
        self.oom_events.append((self.steps, self.rung, self.tier, where))
        self.mm.weight_tier = self.tier
        self.scaler.mark_oom(self.rung)
        if not self._caches_alive():
            # a REAL dispatch OOM can consume the donated cache buffers —
            # rebuild empty rows and replay every in-flight request
            self.caches = self.engine.init_caches(self.rung)
            for req in [r for r in self.slots if r is not None]:
                self._shed(req)
        active = self._active()
        smaller = [r for r in self.engine.rungs if r < self.rung]
        if smaller:
            target = max(smaller)
            while len(active) > target:
                victim = max(active,
                             key=lambda r: (r.admitted_step, r.slot or 0))
                self._shed(victim)
                active.remove(victim)
            if self.task.serves_tokens and self.caches is not None:
                src = np.zeros((target,), np.int32)
                valid = np.zeros((target,), bool)
                for j, req in enumerate(active):
                    src[j], valid[j] = req.slot, True
                self.caches = self.engine.repack(self.rung, target,
                                                 self.caches, src, valid)
            self.slots = list(active) + [None] * (target - len(active))
            for j, req in enumerate(active):
                req.slot = j
            self.rung = target
            self.rung_history.append((self.steps, target))
            return
        lower = [t for t in self.engine.tiers if t < self.tier
                 and (self.rung, t) not in self.mm.poisoned]
        if lower:
            self.set_tier(max(lower), lock=self._tier_locked)
            return
        if active:    # smallest rung, lowest tier: shed the youngest
            victim = max(active, key=lambda r: (r.admitted_step, r.slot or 0))
            self._shed(victim)

    def _first_token(self, req: Request, tok0: int):
        req.tokens = [int(tok0)]
        req.first_token_step = self.steps
        req.first_token_time = time.time()
        self.decoded_tokens += 1
        if len(req.tokens) >= req.max_new_tokens:
            self._finish(req)

    def _pop_next(self) -> Optional[Request]:
        """Next request in scheduler order, priced with the measured
        latency estimates (the SLO scheduler's deadline-feasibility check;
        the FIFO queue ignores the context)."""
        p50 = self.lat.p50(self.rung, self.tier)
        est_step_ms = (p50 or 0.0) * 1e3
        chunk = self.cfg.prefill_chunk or self.cfg.prompt_len

        def admit_ms(req: Request) -> float:
            chunks = -(-max(req.prompt_len, 1) // chunk) if self.chunked else 1
            return est_step_ms * chunks
        return self.queue.pop(now_step=self.steps, est_step_ms=est_step_ms,
                              est_admit_ms=admit_ms)

    def _admit(self):
        # advance in-flight chunked prefills: ONE chunk per request per
        # step, so long prompts interleave with the decodes below
        if self.chunked:
            for req in list(self.slots):
                if req is not None and req.status == "prefilling":
                    if not self._chunk_step(req):
                        return               # OOM: recovery ran this step
        for s in range(self.rung):
            if self.slots[s] is not None or not len(self.queue):
                continue
            req = self._pop_next()
            if req is None:        # everything left was rejected (SLO)
                break
            req.slot = s
            req.admitted_step = self.steps
            self.slots[s] = req
            if self.chunked:
                req.status = "prefilling"
                if not self._chunk_step(req):   # first chunk lands this step
                    return                      # OOM: recovery ran, stop admitting
            else:
                try:
                    if self.fault_plan is not None and self.fault_plan.fires(
                            "serve.step_oom", self.steps, rung=self.rung,
                            tier=self.tier):
                        raise simulated_oom("serve.admit", self.steps)
                    batch1 = {k: v[None] for k, v in req.inputs.items()}
                    tok0, self.caches = self.engine.admit(
                        self.rung, self.tier, self.caches, s, batch1)
                except Exception as e:   # noqa: BLE001 — filtered below
                    if not is_oom_error(e):
                        raise
                    self._shed(req)
                    self._handle_oom("admit")
                    return
                req.status = "active"
                req.index = self.cfg.prompt_len
                self._first_token(req, int(tok0))

    def _chunk_step(self, req: Request) -> bool:
        """Feed the next prefill chunk of ``req`` (pad-to-chunk; pad lanes
        masked inside the executable). The final chunk yields the request's
        first token and flips it to active at index = prompt length.
        Returns False when the dispatch OOM'd (the request was shed and
        recovery ran — the caller stops admitting this step)."""
        C = self.cfg.prefill_chunk
        P = req.prompt_len
        f = req.prefill_pos
        n = min(C, P - f)
        chunk = np.zeros((C,), np.int32)
        chunk[:n] = np.asarray(req.inputs["tokens"][f:f + n], np.int32)
        try:
            if self.fault_plan is not None and self.fault_plan.fires(
                    "serve.step_oom", self.steps, rung=self.rung,
                    tier=self.tier):
                raise simulated_oom("serve.chunk", self.steps)
            tok0, self.caches = self.engine.chunk_admit(
                self.rung, self.tier, self.caches, req.slot, chunk, f, n,
                f == 0)
        except Exception as e:   # noqa: BLE001 — filtered below
            if not is_oom_error(e):
                raise
            self._shed(req)
            self._handle_oom("chunk")
            return False
        req.prefill_pos = f + n
        if req.prefill_pos >= P:
            req.status = "active"
            req.index = P
            self._first_token(req, int(tok0))
        return True

    def _decode(self):
        live = [r for r in self.slots if r is not None and r.status == "active"]
        if not live:
            return
        tokens = np.zeros((self.rung,), np.int32)
        index = np.zeros((self.rung,), np.int32)
        valid = np.zeros((self.rung,), bool)
        for s, req in enumerate(self.slots):
            if req is not None and req.status == "active":
                tokens[s], index[s], valid[s] = req.tokens[-1], req.index, True
        t0 = time.time()
        try:
            if self.fault_plan is not None and self.fault_plan.fires(
                    "serve.step_oom", self.steps, rung=self.rung,
                    tier=self.tier):
                raise simulated_oom("serve.decode", self.steps)
            out, self.caches = self.engine.decode(self.rung, self.tier,
                                                  self.caches, tokens, index,
                                                  valid)
            out = np.asarray(out)  # blocks: the step's real wall time
        except Exception as e:     # noqa: BLE001 — filtered below
            if not is_oom_error(e):
                raise
            # no token landed: positions/caches are unchanged, so the NEXT
            # step() retries this decode bit-identically at the stepped-down
            # (rung, tier)
            self._handle_oom("decode")
            return
        dt = time.time() - t0
        if self.fault_plan is not None:
            spike = self.fault_plan.fires("serve.latency", self.steps,
                                          rung=self.rung, tier=self.tier)
            if spike is not None:
                dt += spike.seconds    # as if the step really stalled
        self.lat.record(self.rung, self.tier, dt)
        for s, req in enumerate(list(self.slots)):
            if req is None or req.status != "active":
                continue
            req.index += 1
            if len(req.tokens) < req.max_new_tokens:
                req.tokens.append(int(out[s]))
                self.decoded_tokens += 1
            if len(req.tokens) >= req.max_new_tokens:
                self._finish(req)

    def _infer(self):
        batch_reqs: List[Request] = []
        while len(self.queue) and len(batch_reqs) < self.rung:
            req = self._pop_next()
            if req is None:
                break
            batch_reqs.append(req)
        if not batch_reqs:
            return
        key = next(iter(self.engine.input_spec))
        shape = self.engine.input_spec[key].shape[1:]
        images = np.zeros((self.rung,) + tuple(shape), np.float32)
        for j, req in enumerate(batch_reqs):
            images[j] = np.asarray(req.inputs[key], np.float32)
        t0 = time.time()
        try:
            if self.fault_plan is not None and self.fault_plan.fires(
                    "serve.step_oom", self.steps, rung=self.rung,
                    tier=self.tier):
                raise simulated_oom("serve.infer", self.steps)
            preds, _ = self.engine.infer(self.rung, self.tier, {key: images})
            preds = np.asarray(preds)
        except Exception as e:     # noqa: BLE001 — filtered below
            if not is_oom_error(e):
                raise
            for req in batch_reqs:     # vision reqs hold no slot/cache rows
                self._shed(req)
            self._handle_oom("infer")
            return
        dt = time.time() - t0
        if self.fault_plan is not None:
            spike = self.fault_plan.fires("serve.latency", self.steps,
                                          rung=self.rung, tier=self.tier)
            if spike is not None:
                dt += spike.seconds
        self.lat.record(self.rung, self.tier, dt)
        for j, req in enumerate(batch_reqs):
            req.status = "active"
            req.admitted_step = self.steps
            req.result = int(preds[j])
            req.first_token_step = self.steps
            req.first_token_time = time.time()
            self._finish(req)
