"""Tri-Accel §3.3 — Memory-Elastic Batch Scaling, TPU realization.

The paper polls ``cuda.memory_allocated`` and nudges the batch size by
±delta. On TPU there is no cheap in-step memory query and a new batch shape
means a new executable, so the controller is re-based on two pieces:

  * ``MemoryModel`` — an analytic per-device HBM estimate
    (params + optimizer + gradient + activation(tokens, precision codes)),
    plus a rung-indexed MEASURED overlay harvested from
    ``compiled.memory_analysis()`` of the AOT-warmed executables. Rung
    predictions are measured-first: a rung that has been observed (warmed or
    stepped) answers with its real footprint, an unobserved rung answers
    with the analytic model re-fit (``calibration``) to the latest
    measurement — the paper's closed loop over measured VRAM instead of an
    open-loop analytic guess;
  * ``BatchScaler`` — the paper's hysteresis law over a discrete rung ladder
    of per-device microbatch sizes whose step functions are AOT-compiled
    once, so a rung change is a zero-stall dictionary lookup.

The control law is the paper's:
    B += delta_up    if mem < rho_low  * cap
    B -= delta_down  if mem > rho_high * cap
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.precision import TriAccelConfig

#: The CPU backend reports no memory limit; its (test) path models one
#: 16 GB device. Accelerators must report their own.
CPU_MEM_CAP = 16e9


def device_mem_cap(device) -> float:
    """Per-device memory limit as ``device`` reports it
    (``memory_stats()["bytes_limit"]``). An accelerator that reports none
    is an error, not a default."""
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        return float(stats["bytes_limit"])
    if device.platform == "cpu":
        return CPU_MEM_CAP
    raise RuntimeError(
        f"{device.platform} device {device.device_kind!r} reports no "
        "memory_stats()['bytes_limit']; pass mem_cap_bytes explicitly")


def with_device_cap(cfg: TriAccelConfig, device) -> TriAccelConfig:
    """``cfg`` with ``mem_cap_bytes`` filled from ``device`` when unset."""
    if cfg.mem_cap_bytes is not None:
        return cfg
    return dataclasses.replace(cfg, mem_cap_bytes=device_mem_cap(device))


def measured_exe_bytes(compiled) -> Optional[float]:
    """Per-host HBM footprint of one AOT executable from XLA's
    ``memory_analysis()``: temp + argument + output + generated code, with
    donated (aliased) buffers counted once. ``None`` when the backend
    reports nothing (the caller falls back to the analytic model)."""
    mem = compiled.memory_analysis()
    if mem is None:
        return None
    fields = ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "generated_code_size_in_bytes")
    vals = [getattr(mem, f, None) for f in fields]
    if all(v is None for v in vals):
        return None
    total = float(sum(v for v in vals if v is not None))
    total -= float(getattr(mem, "alias_size_in_bytes", 0) or 0)
    return total

# bytes per element of each precision tier (low tier: fp8=1 on tpu, fp16=2 on gpu)
TIER_BYTES = {"gpu": (2.0, 2.0, 4.0), "tpu": (1.0, 2.0, 4.0)}


@dataclasses.dataclass
class MemoryModel:
    """Per-device HBM footprint model (bytes)."""

    param_count: float                 # per-device parameters (after sharding)
    opt_slots: int = 2                 # fp32 master + momentum (SGD-M); 3 for Adam
    act_bytes_per_token_layer: float = 0.0   # remat-adjusted, tier-1 (bf16)
    num_layers: int = 1
    fixed_overhead: float = 256e6
    calibration: float = 1.0           # fitted against memory_analysis()
    #: rung-indexed measured overlay (``measured_key(rung)`` -> bytes),
    #: populated from memory_analysis() of the warmed executables. The last
    #: measurement per rung wins; entries are per the CURRENT precision codes
    #: (a code change is folded in through ``calibration`` on re-measure).
    measured: Dict[Any, float] = dataclasses.field(default_factory=dict)
    #: keys a backend RESOURCE_EXHAUSTED has condemned (BatchScaler.mark_oom):
    #: their overlay entries are pinned above the device cap and are never
    #: overwritten by later measurements — memory_analysis() said the
    #: executable fit, the allocator said otherwise, and the allocator wins.
    poisoned: set = dataclasses.field(default_factory=set)

    @classmethod
    def for_transformer(cls, param_count, d_model, num_layers, opt_slots=2,
                        remat=True):
        # with block remat only block boundaries are resident:
        # ~2.5 activations of width d_model per layer per token (bf16 = 2B)
        act = (2.5 if remat else 14.0) * d_model * 2.0
        return cls(param_count=param_count, opt_slots=opt_slots,
                   act_bytes_per_token_layer=act, num_layers=num_layers)

    def param_state_bytes(self) -> float:
        # bf16 compute copy + fp32 master + opt slots fp32 + bf16 grads
        return self.param_count * (2.0 + 4.0 + 4.0 * self.opt_slots + 2.0)

    def activation_bytes(self, tokens_per_device: float,
                         codes=None, ladder: str = "gpu") -> float:
        scale = 1.0
        if codes is not None and len(codes) > 0:
            tiers = TIER_BYTES[ladder]
            mean_bytes = sum(tiers[int(c)] for c in codes) / len(codes)
            scale = mean_bytes / 2.0   # relative to bf16 baseline
        return (self.act_bytes_per_token_layer * self.num_layers *
                tokens_per_device * scale)

    def total(self, tokens_per_device: float, codes=None,
              ladder: str = "gpu") -> float:
        return self.calibration * (
            self.param_state_bytes()
            + self.activation_bytes(tokens_per_device, codes, ladder)
            + self.fixed_overhead)

    def calibrate(self, measured_bytes: float, tokens_per_device: float,
                  codes=None, ladder: str = "gpu") -> None:
        # non-positive measurements carry no scale information and would
        # zero the calibration factor (poisoning every later re-fit)
        if measured_bytes <= 0:
            return
        est = self.total(tokens_per_device, codes, ladder) / self.calibration
        # a subnormal measurement can underflow the ratio to 0 as well
        if est > 0 and measured_bytes / est > 0:
            self.calibration = measured_bytes / est

    # ------------------------------------------- measured-bytes overlay ---
    def measured_key(self, rung: int):
        """Overlay key for one rung (subclasses add the precision tier)."""
        return rung

    def record_measured(self, rung: int, measured_bytes: float,
                        tokens_per_device: float, codes=None,
                        ladder: str = "gpu") -> None:
        """Store the observed footprint for ``rung`` AND re-fit the analytic
        calibration, so predictions for still-unmeasured rungs move
        consistently with what was just measured (the climb guard can never
        disagree with the observation that triggered it). Non-positive
        observations carry no information and are dropped — a 0-byte overlay
        entry would pin predict() below rho_low forever. Poisoned keys
        (mark_oom) are immutable: the pre-OOM measurement that is being
        re-reported is exactly the optimistic number that OOM'd."""
        if measured_bytes <= 0 or self.measured_key(rung) in self.poisoned:
            return
        self.measured[self.measured_key(rung)] = float(measured_bytes)
        self.calibrate(measured_bytes, tokens_per_device, codes, ladder)

    def predict(self, rung: int, tokens_per_device: float, codes=None,
                ladder: str = "gpu") -> float:
        """Measured-first footprint for ``rung``: the overlay entry when this
        rung has been observed, the calibrated analytic model otherwise."""
        m = self.measured.get(self.measured_key(rung))
        return m if m is not None else self.total(tokens_per_device, codes,
                                                  ladder)


@dataclasses.dataclass
class ServeMemoryModel(MemoryModel):
    """Inference-time HBM model: weights at the ACTIVE serving precision tier
    (fp8 / bf16 / fp32 per ``TIER_BYTES``) plus per-sequence decode-cache
    bytes carried in ``act_bytes_per_token_layer`` — no optimizer, master, or
    gradient state. Drives both the §3.3 batch-rung controller and the
    precision-adaptive decode tier selection (repro.serve.session)."""

    weight_tier: int = 1               # serving precision code: 0/1/2
    ladder: str = "tpu"

    def param_state_bytes(self) -> float:
        return self.param_count * TIER_BYTES[self.ladder][self.weight_tier]

    def measured_key(self, rung: int):
        """Serve footprints differ per decode-weight tier, so the overlay is
        keyed (rung, tier) — matching the engine's AOT cache keys."""
        return (rung, self.weight_tier)


class BatchScaler:
    """Discrete-rung realization of the paper's VRAM feedback controller."""

    def __init__(self, rungs: Sequence[int], seq_len: int, model: MemoryModel,
                 cfg: TriAccelConfig, start_rung: Optional[int] = None):
        assert list(rungs) == sorted(set(rungs)) and len(rungs) > 0
        if cfg.mem_cap_bytes is None:
            cfg = with_device_cap(cfg, jax.devices()[0])
        self.rungs = list(rungs)
        self.seq_len = seq_len
        self.model = model
        self.cfg = cfg
        self.idx = len(rungs) - 1 if start_rung is None else rungs.index(start_rung)
        # never start on a rung the model says won't fit
        while self.idx > 0 and self._mem(self.idx) > cfg.rho_high * cfg.mem_cap_bytes:
            self.idx -= 1
        self.history: List[Tuple[int, int, float]] = []  # (step, rung, mem)

    @property
    def microbatch(self) -> int:
        return self.rungs[self.idx]

    def _mem(self, idx: int, codes=None) -> float:
        """Measured-first footprint prediction for rung index ``idx``."""
        return self.model.predict(self.rungs[idx],
                                  self.rungs[idx] * self.seq_len, codes,
                                  self.cfg.ladder)

    def _cap_index(self, rung_cap: Optional[int]) -> Optional[int]:
        """Index of the largest rung <= ``rung_cap`` (0 when the cap is
        below every configured rung — the ceiling throttles, it never makes
        the ladder empty)."""
        if rung_cap is None:
            return None
        idx = 0
        for i, r in enumerate(self.rungs):
            if r <= rung_cap:
                idx = i
        return idx

    def mark_oom(self, rung: Optional[int] = None) -> int:
        """React to a backend RESOURCE_EXHAUSTED on ``rung``'s executable
        (repro.resilience recovery supervision). The rung is poisoned in the
        measured overlay at 2x the device cap — above ``rho_high * cap``, so
        the measured-first climb guard can never re-enter it, and
        ``record_measured`` never replaces the poison with a stale pre-OOM
        harvest — and the controller steps ``delta_down`` rungs below it.
        Returns the new microbatch; unchanged when the OOM'd rung is already
        the smallest (the caller escalates to checkpoint-and-exit)."""
        rung = self.microbatch if rung is None else rung
        key = self.model.measured_key(rung)
        self.model.poisoned.add(key)
        self.model.measured[key] = 2.0 * self.cfg.mem_cap_bytes
        if rung in self.rungs:
            i = self.rungs.index(rung)
            if self.idx >= i:
                self.idx = max(i - self.cfg.delta_down, 0)
        return self.microbatch

    def observe(self, step: int, codes=None,
                measured_bytes: Optional[float] = None,
                rung_cap: Optional[int] = None) -> int:
        """Apply the paper's hysteresis law; returns the (possibly new) rung.

        ``measured_bytes`` (harvested ``memory_analysis()`` of the current
        rung's executable, max over hosts) closes the loop: it is recorded
        into the model's rung overlay and re-fits the analytic calibration,
        so the climb guard's next-rung prediction is CALIBRATED — measured
        when the next rung was warmed, measurement-scaled analytic otherwise
        — and can no longer disagree with the observation (the uncalibrated
        guard oscillated: climb on optimistic analytic, back off on the
        measurement, repeat).

        ``rung_cap`` is the latency ceiling (repro.serve.scheduler
        .LatencyTable.latency_rung): the largest rung whose modeled p99
        step time fits the tightest SLO class budget. The climb guard never
        crosses it, and a rung already above it steps down — the latency
        twin of the memory law, sharing its hysteresis cadence."""
        if not self.cfg.enable_batch:
            return self.microbatch
        if measured_bytes is not None:
            self.model.record_measured(self.rungs[self.idx], measured_bytes,
                                       self.rungs[self.idx] * self.seq_len,
                                       codes, self.cfg.ladder)
            mem = float(measured_bytes)
        else:
            mem = self._mem(self.idx, codes)
        cap = self.cfg.mem_cap_bytes
        cap_i = self._cap_index(rung_cap)
        if mem < self.cfg.rho_low * cap and self.idx + 1 < len(self.rungs):
            nxt = min(self.idx + self.cfg.delta_up, len(self.rungs) - 1)
            if cap_i is not None:
                nxt = min(nxt, cap_i)
            # only climb if the calibrated model predicts the next rung fits
            if nxt > self.idx and self._mem(nxt, codes) <= self.cfg.rho_high * cap:
                self.idx = nxt
        elif mem > self.cfg.rho_high * cap and self.idx > 0:
            self.idx = max(self.idx - self.cfg.delta_down, 0)
        if cap_i is not None and self.idx > cap_i:
            self.idx = max(self.idx - self.cfg.delta_down, cap_i)
        self.history.append((step, self.microbatch, mem))
        return self.microbatch
