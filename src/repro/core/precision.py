"""Tri-Accel §3.1 — Precision-Adaptive Updates.

Per-layer precision codes (0 = low tier, 1 = bf16, 2 = fp32) are selected
from an EMA of per-layer gradient variance against thresholds (tau_low,
tau_high), with §3.2's curvature promotion overriding to fp32 above tau_curv.

On TPU the precision *assignment algorithm* is identical to the paper's; the
*actuation* differs (see DESIGN.md §2): in the single-graph dynamic mode a
precision code selects a value-level quantize-dequantize (``qdq``) via
``lax.switch`` — weights are rounded to the target format's grid while the
container dtype stays static, so the policy can change every control window
with zero recompilation. The static-bucket mode (repro.train.train_step)
AOT-compiles real-dtype variants for the K policy buckets.

Ladders:
    gpu: fp16 / bf16 / fp32   (paper-faithful)
    tpu: fp8_e4m3 (per-tensor amax scaling) / bf16 / fp32
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

LADDERS = {"gpu": ("fp16", "bf16", "fp32"), "tpu": ("fp8", "bf16", "fp32")}

FP8_MAX = 448.0  # float8_e4m3fn max normal


@dataclasses.dataclass(frozen=True)
class TriAccelConfig:
    # §3.1 precision
    beta: float = 0.9                   # variance EMA smoothing
    tau_low: float = 1e-6               # v < tau_low  -> low tier
    tau_high: float = 1e-3              # v >= tau_high -> fp32
    ladder: str = "gpu"
    dynamic_precision: bool = True      # False -> static bf16 (AMP baseline)
    stochastic_round: bool = False      # SR on the fused compute cast
                                        # (bf16 container casts only)
    # §3.2 curvature
    curvature_method: str = "hutchinson"   # "power" | "hutchinson" | "fisher"
    top_k: int = 5
    power_iters: int = 5
    t_curv: int = 200                   # curvature refresh period (steps)
    b_curv: int = 32                    # curvature micro-batch
    alpha: float = 0.1                  # lr scale: eta/(1 + alpha*lam)
    tau_curv: float = 10.0              # promote to fp32 above this curvature
    # §3.3 memory-elastic batch
    rho_low: float = 0.80
    rho_high: float = 0.92
    delta_up: int = 1                   # rung steps, paper's delta_up/down
    delta_down: int = 1
    #: per-device memory cap; None = the device's own limit
    #: (batch_scaler.device_mem_cap)
    mem_cap_bytes: Optional[float] = None
    # §3.4 control loop
    t_ctrl: int = 50
    # ablation switches (paper Table 2)
    enable_precision: bool = True
    enable_curvature: bool = True
    enable_batch: bool = True


def check_ladder_kernels(ladder: str, platform: str) -> None:
    """The gpu ladder's fp16 low tier has no TPU kernel: Mosaic cannot
    lower the f32 -> f16 cast (``tpu.pack_subelements``). Refuse at
    construction, before any compile, instead of switching tiers."""
    if ladder == "gpu" and platform == "tpu":
        raise ValueError(
            "ladder='gpu' (fp16 low tier) has no Pallas TPU lowering: Mosaic "
            "cannot cast to float16. Use ladder='tpu' (fp8 low tier) on TPU.")


# ------------------------------------------------------------------ QDQ ----
def _qdq_fp16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.float16).astype(x.dtype)


def _qdq_bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(x.dtype)


@jax.custom_jvp
def _qdq_fp8(x: jax.Array) -> jax.Array:
    """Per-tensor amax-scaled e4m3 rounding (TPU-native low tier).

    Straight-through: the derivative is the identity. Differentiating the
    casts themselves would round the COTANGENT onto the unscaled e4m3 grid
    (no inf, NaN above 448, zero below 2^-9), so every low-tier step would
    come back non-finite and be skipped; the fused path differentiates
    against the already-rounded compute copy, which is this same rule."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    y = (x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
    return (y.astype(jnp.float32) / scale).astype(x.dtype)


@_qdq_fp8.defjvp
def _qdq_fp8_jvp(primals, tangents):
    return _qdq_fp8(primals[0]), tangents[0]


def _identity(x: jax.Array) -> jax.Array:
    return x


def qdq(x: jax.Array, code: jax.Array, ladder: str = "gpu") -> jax.Array:
    """Round ``x`` to the grid of the precision tier selected by ``code``.

    Gradients pass straight through the rounding, matching mixed-precision
    master-weight semantics: the fp16/bf16 casts are linear in JAX (the
    cotangent takes the tier's dtype), the fp8 tier is straight-through by
    its custom derivative.
    """
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    low = _qdq_fp8 if ladder == "tpu" else _qdq_fp16
    mid = _identity if x.dtype == jnp.bfloat16 else _qdq_bf16
    return jax.lax.switch(jnp.asarray(code, jnp.int32), [low, mid, _identity], x)


def make_qdq_fn(cfg: TriAccelConfig) -> Optional[Callable]:
    """QDQ is applied whenever dynamic_precision is on; enable_precision
    only gates whether the codes ADAPT (False freezes them at the bf16
    tier = the paper's static-AMP baseline)."""
    if not cfg.dynamic_precision:
        return None
    return partial(qdq, ladder=cfg.ladder)


# -------------------------------------------------- variance statistics ----
def moment_stats(tree, layer_axis: bool = False):
    """(sum, sumsq, count) over a layer's gradient leaves.

    With ``layer_axis`` the leaves carry a leading stacked-layer dim that is
    preserved: returns per-layer (n,) vectors — the whole segment's variance
    statistics in one pass (this is what the grad_stats Pallas kernel fuses
    on TPU).
    """
    leaves = [l for l in jax.tree.leaves(tree)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    if layer_axis:
        s = sum(jnp.sum(l.astype(jnp.float32), axis=tuple(range(1, l.ndim)))
                for l in leaves)
        ss = sum(jnp.sum(jnp.square(l.astype(jnp.float32)),
                         axis=tuple(range(1, l.ndim))) for l in leaves)
        cnt = sum(float(l.size) / l.shape[0] for l in leaves)
        cnt = jnp.full_like(s, cnt)
    else:
        s = sum(jnp.sum(l.astype(jnp.float32)) for l in leaves)
        ss = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
        cnt = jnp.asarray(float(sum(l.size for l in leaves)), jnp.float32)
    return s, ss, cnt


def variance_from_moments(s, ss, cnt):
    mean = s / jnp.maximum(cnt, 1.0)
    return jnp.maximum(ss / jnp.maximum(cnt, 1.0) - jnp.square(mean), 0.0)


def ema_update(v_prev, v_now, beta):
    return beta * v_prev + (1.0 - beta) * v_now


def codes_from_stats(var_ema: jax.Array, lam: jax.Array,
                     cfg: TriAccelConfig) -> jax.Array:
    """§3.1 threshold rule + §3.2 curvature promotion -> (L,) int32 codes."""
    codes = jnp.where(var_ema < cfg.tau_low, 0,
                      jnp.where(var_ema < cfg.tau_high, 1, 2)).astype(jnp.int32)
    if cfg.enable_curvature:
        codes = jnp.maximum(codes, jnp.where(lam > cfg.tau_curv, 2, 0))
    if not cfg.enable_precision:
        codes = jnp.ones_like(codes)  # static bf16 (AMP)
    return codes
