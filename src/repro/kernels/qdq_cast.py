"""Fused quantize-dequantize cast kernel (the paper's Triton precision
kernel, adapted to TPU).

Rounds a tensor to the grid of the Tri-Accel precision tier selected by a
runtime code (0 = low tier, 1 = bf16, 2 = keep), in one kernel launch over
VMEM tiles. The low tier is fp8_e4m3 with a per-tensor amax scale (tpu
ladder) or fp16 (gpu ladder). The code (and amax) live in SMEM, so one
compiled kernel serves every layer / control-window decision — precision
changes never recompile.

The tpu ladder's amax reduction is FUSED into the kernel as a two-phase
grid: phase 0 sweeps the tiles accumulating |x|max into SMEM scratch,
phase 1 re-sweeps applying the cast with the scale derived in-kernel — no
separate jnp pass over ``x`` materializes before launch. Callers that
already hold the tensor's absmax (e.g. from ``grad_stats``) pass it as
``amax`` and get the single-phase grid; the gpu ladder needs no amax and is
always single-phase.

Tiling: (BLOCK_M, BLOCK_N) = (256, 512) fp32 tiles -> 0.5 MiB in + 0.5 MiB
out per step, well inside the ~16 MiB/core VMEM budget, with the trailing
dim a multiple of 128 lanes and the leading a multiple of the 8-row sublane.
Block-aligned sizes (the weight-matrix common case) reshape in place; only
ragged tails take the zero-pad copy (kernels.layout.fold2d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import fold2d

FP8_MAX = 448.0
BLOCK_M = 256
BLOCK_N = 512


def _tier_select(x, code, scale, ladder: str):
    if ladder == "tpu":
        low = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        low = x.astype(jnp.float16).astype(jnp.float32)
    mid = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(code == 0, low, jnp.where(code == 1, mid, x))


def _qdq_kernel(code_ref, scale_ref, x_ref, o_ref, *, ladder: str):
    """Single-phase: scale precomputed by the caller (gpu ladder / amax
    supplied from grad_stats)."""
    x = x_ref[...].astype(jnp.float32)
    out = _tier_select(x, code_ref[0], scale_ref[0], ladder)
    o_ref[...] = out.astype(o_ref.dtype)


def _qdq_fused_kernel(code_ref, x_ref, o_ref, amax_ref, *, ladder: str):
    """Two-phase grid (phase, tile): phase 0 reduces |x|max into SMEM,
    phase 1 casts with the in-kernel scale. Output tiles written during
    phase 0 are placeholders; the sequential grid rewrites every tile in
    phase 1, so the last write per tile is the real value."""
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _init():
        amax_ref[0] = 0.0

    @pl.when(p == 0)
    def _reduce():
        amax_ref[0] = jnp.maximum(
            amax_ref[0], jnp.max(jnp.abs(x_ref[...].astype(jnp.float32))))

    @pl.when(p == 1)
    def _apply():
        amax = amax_ref[0]
        scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
        x = x_ref[...].astype(jnp.float32)
        o_ref[...] = _tier_select(x, code_ref[0], scale, ladder).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ladder", "interpret"))
def qdq_cast(x: jax.Array, code: jax.Array, ladder: str = "tpu",
             interpret: bool = False, amax: jax.Array = None) -> jax.Array:
    """Round ``x`` (any shape) to the tier grid selected by ``code``.

    ``amax``: optional precomputed max(|x|) (e.g. the ``grad_stats`` absmax)
    — skips the in-kernel reduction phase for the tpu ladder."""
    orig_shape = x.shape
    n = x.size
    x2 = fold2d(x, BLOCK_M, BLOCK_N)
    nb = x2.shape[0] // BLOCK_M
    code = jnp.asarray(code, jnp.int32).reshape(1)

    if ladder == "tpu" and amax is None:
        out = pl.pallas_call(
            functools.partial(_qdq_fused_kernel, ladder=ladder),
            grid=(2, nb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),           # code
                pl.BlockSpec((BLOCK_M, BLOCK_N), lambda p, i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((BLOCK_M, BLOCK_N), lambda p, i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
            interpret=interpret,
        )(code, x2)
    else:
        if ladder == "tpu":
            amax = jnp.asarray(amax, jnp.float32)
            scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
        else:
            scale = jnp.float32(1.0)               # gpu ladder: unused
        out = pl.pallas_call(
            functools.partial(_qdq_kernel, ladder=ladder),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),           # code
                pl.BlockSpec(memory_space=pltpu.SMEM),           # scale
                pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((BLOCK_M, BLOCK_N), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
            interpret=interpret,
        )(code, scale.reshape(1), x2)
    return out.reshape(-1)[:n].reshape(orig_shape)
