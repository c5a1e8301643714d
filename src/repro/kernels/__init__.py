"""Pallas TPU kernels for Tri-Accel's compute hot spots.

qdq_cast.py        — fused per-tensor amax + round-to-tier + cast in one
                     launch (the paper's Triton precision kernel, TPU-tiled;
                     two-phase grid folds the amax reduction in)
grad_stats.py      — one-pass fused sum / sum-of-squares / absmax reduction
                     (feeds the per-layer gradient-variance EMA), with a
                     small-tile path for sub-block leaves
flash_attention.py — block-tiled online-softmax attention with causal +
                     sliding-window block skipping (the LM hot spot),
                     forward AND backward (dQ / dK-dV kernels), plus the
                     ragged per-slot-length decode kernel
fused_update.py    — the whole post-backward update phase as two slab
                     sweeps: per-layer stats + finite + norm (phase 1),
                     then clip + optimizer + fp32 master write + next-step
                     low-precision cast in the same tile (phase 2)
layout.py          — shared (rows, BLOCK_N) folding with an alignment fast
                     path (no pad copy for block-aligned tensors) and the
                     SlabView tree->slab layout the fused update sweeps

ops.py exposes jit'd wrappers (interpret=True off-TPU) and binds the flash
kernels into one differentiable op (jax.custom_vjp) behind the dispatch
gate; ref.py holds the pure-jnp oracles the tests sweep against.
"""
from repro.kernels import ops, ref
