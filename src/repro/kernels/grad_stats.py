"""Fused gradient-statistics kernel: one pass -> (sum, sum_sq, absmax).

Feeds Tri-Accel's per-layer gradient-variance EMA (§3.1). The jnp fallback
reads the gradient three times; this kernel reads each VMEM tile once and
accumulates all three moments in fp32. The output block index_map is
constant, so the accumulator stays resident across the sequential TPU grid;
iteration 0 initializes it. It is one (1, 128) lane vector holding
(sum, sum_sq, absmax) in lanes 0-2: the TPU stores vectors, not scalars,
to VMEM. Block-aligned sizes reshape in place;
only ragged tails take the zero-pad copy (kernels.layout.fold2d), and
sub-block tensors (biases, norm scales) take a SMALL single tile
(kernels.layout.small_blocks) instead of being zero-padded to the full
256x512 = 128K-element block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import fold2d, small_blocks

BLOCK_M = 256
BLOCK_N = 512


def _stats_kernel(x_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    upd = jnp.where(lane == 0, jnp.sum(x),
                    jnp.where(lane == 1, jnp.sum(jnp.square(x)),
                              jnp.where(lane == 2, jnp.max(jnp.abs(x)), 0.0)))

    @pl.when(i == 0)
    def _init():
        o_ref[...] = upd

    @pl.when(i > 0)
    def _acc():
        prev = o_ref[...]
        o_ref[...] = jnp.where(lane == 2, jnp.maximum(prev, upd), prev + upd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grad_stats(x: jax.Array, interpret: bool = False):
    """Returns (sum, sum_sq, absmax) of ``x`` as fp32 scalars."""
    bm, bn = small_blocks(x.size, BLOCK_M, BLOCK_N)
    x2 = fold2d(x, bm, bn, min_rows=bm)
    out = pl.pallas_call(
        _stats_kernel,
        grid=(x2.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, bn), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
        interpret=interpret,
    )(x2)
    return out[0, 0], out[0, 1], out[0, 2]
