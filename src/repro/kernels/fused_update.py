"""Fused update phase: one Pallas slab sweep for stats + clip + optimizer +
master update + next-step cast (DESIGN.md §9).

The reference post-backward path is six independent HBM passes over the
full gradient footprint (``_tree_finite``, ``global_norm``, clip,
``grouping.moments``, ``opt.update``, ``apply_updates`` in
repro.train.train_step) plus a seventh full read in the next step's
``cast_params``. This module replaces all of them with TWO slab sweeps
over the ``SlabView`` layout (kernels.layout):

  phase 1  ``stats_kernel``   — reads each gradient tile once, reduces
           per-row (sum, sum_sq, absmax, nonfinite) and segment-combines
           them into per-LAYER accumulators in-kernel via masked row
           reductions against the static per-row layer ids (subsuming
           grad_stats, _tree_finite and global_norm: the global sq-norm is
           the sum of the per-layer sum_sq, the finite gate is
           nonfinite == 0).

  (scalar combine, jnp, O(L))  — loss-scale/accum unscale, global clip
           coefficient, variance-EMA control update, curvature-scaled lr
           table, next codes, fp8 cast scales from the carried per-layer
           param absmax.

  phase 2  ``apply_kernel``   — reads each gradient tile a second (final)
           time together with the master/momentum tiles and applies
           unscale -> clip -> momentum/Adam moment update -> per-row
           curvature-scaled lr step -> fp32 master write -> and, in the
           same tile, the next step's low-precision compute copy (the
           qdq_cast tier-select math with per-row cast scales), while
           max-accumulating the per-layer absmax of the fresh compute
           copy — next step's fp8 scales, one step delayed (standard
           delayed-scaling semantics; the reference path re-reduces a
           fresh per-tensor amax instead).

Per-layer control scalars reach the kernels as per-row metadata gathered
outside (footprint/SLAB_N elements — negligible): one (8, SLAB_M) f32 block
per tile holding each row's lr, precision code, cast scale and layer id
along lanes (``row_meta``). The kernels turn it into (SLAB_M, 8) columns
with one transpose per tile, so precision codes, lr scales and cast scales
are all runtime values: one compiled kernel serves every control decision
with zero recompiles. Per-layer results come back lane-major as one
(8, LP) block — layer l in lane l, one statistic per sublane row — reduced
over the tile's rows with a (SLAB_M, LP) row->layer mask.

Gradient-footprint traffic: 2 reads + 2 writes (master + compute copy)
versus >= 6 reads + 4 writes on the reference path —
``roofline.costmodel.update_phase_bytes`` is the shared byte model.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.layout import SLAB_M, SLAB_N, SlabView

FP8_MAX = 448.0

# per-layer statistic rows of the (8, LP) phase-1 output block
ROW_SUM, ROW_SQ, ROW_NF, ROW_MAX = 0, 1, 2, 3
# per-row metadata rows of the (8, SLAB_M) block (``row_meta``)
META_LR, META_CODE, META_QS, META_LAYER = 0, 1, 2, 3


def _lanes(num_layers: int) -> int:
    """Lane width of the per-layer output block (layer l in lane l)."""
    return -(-num_layers // 128) * 128


def row_meta(row_layer, lr_rows=None, code_rows=None, qs_rows=None):
    """(n_tiles, 8, SLAB_M) f32 per-row metadata: rows META_* hold each slab
    row's lr, precision code, cast scale and layer id (all exact in f32);
    the per-row inputs are (n_tiles, SLAB_M) blocks (SlabView.row_blocks /
    gather_rows). One 8 KiB block per grid step, read lane-major."""
    rows = [jnp.zeros(row_layer.shape, jnp.float32)] * 8
    for r, x in ((META_LR, lr_rows), (META_CODE, code_rows),
                 (META_QS, qs_rows), (META_LAYER, row_layer)):
        if x is not None:
            rows[r] = x.astype(jnp.float32).reshape(row_layer.shape)
    return jnp.stack(rows, axis=1)


def _meta_cols(meta_ref):
    """The tile's (8, SLAB_M) metadata block as (SLAB_M, 8) columns."""
    return jnp.transpose(meta_ref[...])


def _layer_mask(cols, lanes: int):
    """(SLAB_M, lanes) bool: row r belongs to layer l (lane l)."""
    ids = cols[:, META_LAYER:META_LAYER + 1].astype(jnp.int32)
    return jax.lax.broadcasted_iota(jnp.int32, (SLAB_M, lanes), 1) == ids


def _per_layer(mask, col, reduce):
    """Reduce a (SLAB_M, 1) per-row column into (1, lanes) per-layer values
    (rows of other layers contribute 0: every statistic here is >= 0 or a
    sum)."""
    return reduce(jnp.where(mask, col, 0.0), axis=0, keepdims=True)


def _stack_rows(vals, lanes: int):
    """Place (1, lanes) rows 0..len(vals)-1 of an (8, lanes) block."""
    r = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0)
    out = jnp.zeros((8, lanes), jnp.float32)
    for i, v in enumerate(vals):
        out = jnp.where(r == i, v, out)
    return out


def _lane_block(lanes: int):
    return pl.BlockSpec((8, lanes), lambda i: (0, 0))


def _meta_block():
    return pl.BlockSpec((None, 8, SLAB_M), lambda i: (i, 0, 0))


# =============================================================== phase 1 ===
def _stats_kernel(meta_ref, x_ref, acc_ref, *, lanes: int):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                       # (SLAB_M, SLAB_N)
    ok = jnp.isfinite(x)
    # non-finite lanes are COUNTED (rnf drives the global skip gate) but
    # excluded from the moments: a raw inf/nan would reach every layer's
    # masked reduction as 0*inf = NaN and permanently poison the whole
    # var_ema (the jnp reference merely NaNs the offending layer; the fused
    # path keeps even that layer's EMA alive across overflow steps — the
    # skipped step contributes its finite lanes only)
    xf = jnp.where(ok, x, 0.0)
    rs = jnp.sum(xf, axis=1, keepdims=True)                  # (SLAB_M, 1)
    rss = jnp.sum(jnp.square(xf), axis=1, keepdims=True)
    rnf = jnp.sum(jnp.where(ok, 0.0, 1.0), axis=1, keepdims=True)
    rmx = jnp.max(jnp.abs(xf), axis=1, keepdims=True)

    mask = _layer_mask(_meta_cols(meta_ref), lanes)
    upd = _stack_rows([_per_layer(mask, rs, jnp.sum),
                       _per_layer(mask, rss, jnp.sum),
                       _per_layer(mask, rnf, jnp.sum),
                       _per_layer(mask, rmx, jnp.max)], lanes)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = upd

    @pl.when(i > 0)
    def _acc():
        prev = acc_ref[...]
        is_max = jax.lax.broadcasted_iota(jnp.int32, (8, lanes), 0) == ROW_MAX
        acc_ref[...] = jnp.where(is_max, jnp.maximum(prev, upd), prev + upd)


@functools.partial(jax.jit, static_argnames=("num_layers", "interpret"))
def fused_stats(g_slab: jax.Array, row_layer: jax.Array, num_layers: int,
                interpret: bool = False):
    """One gradient read -> per-layer (sum, sum_sq, absmax, nonfinite).

    ``row_layer`` is the SlabView's static (n_tiles, SLAB_M) layer-id
    blocks. Returns four (num_layers,) fp32 vectors."""
    lanes = _lanes(num_layers)
    nb = g_slab.shape[0] // SLAB_M
    acc = pl.pallas_call(
        functools.partial(_stats_kernel, lanes=lanes),
        grid=(nb,),
        in_specs=[_meta_block(),
                  pl.BlockSpec((SLAB_M, SLAB_N), lambda i: (i, 0))],
        out_specs=_lane_block(lanes),
        out_shape=jax.ShapeDtypeStruct((8, lanes), jnp.float32),
        interpret=interpret,
    )(row_meta(row_layer), g_slab)
    L = num_layers
    return (acc[ROW_SUM, :L], acc[ROW_SQ, :L], acc[ROW_MAX, :L],
            acc[ROW_NF, :L])


# =============================================================== phase 2 ===
class OptSpec(NamedTuple):
    """Static optimizer hyperparameters the kernel specializes on (carried
    on ``Optimizer.spec`` by repro.optim.optimizers)."""
    kind: str                   # "sgdm" | "adamw"
    momentum: float = 0.9
    nesterov: bool = False
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0


def _sr_bits(tile: int, seed):
    """Counter-based PRNG: one uint32 per lane, a murmur3-finalizer mix of
    (global row, lane, step seed). Pure vector ops, so the SAME stream is
    produced on TPU mosaic and in interpret mode — SR trajectories are
    reproducible across backends at a fixed seed."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (SLAB_M, SLAB_N), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (SLAB_M, SLAB_N), 1)
    r = r + jnp.uint32(tile * SLAB_M) if isinstance(tile, int) else \
        r + tile.astype(jnp.uint32) * jnp.uint32(SLAB_M)
    h = (r * jnp.uint32(0x9E3779B9)) ^ (c * jnp.uint32(0x85EBCA6B)) \
        ^ (seed * jnp.uint32(0xC2B2AE35))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _sr_to_bf16(pn, bits):
    """Stochastic round fp32 -> the bf16 grid, bitwise: add the low 16
    random bits to the fp32 pattern, truncate the mantissa tail. Unbiased
    (P(up) = tail/2^16) and exact when pn is already on the grid. Works
    only because bf16 is a bit-truncation of fp32 — f16 ladders keep RTN."""
    u = jax.lax.bitcast_convert_type(pn, jnp.uint32)
    usr = (u + (bits & jnp.uint32(0xFFFF))) & jnp.uint32(0xFFFF0000)
    snapped = jax.lax.bitcast_convert_type(usr, jnp.float32)
    # inf/nan bit patterns must not be perturbed (inf + rand = nan bits)
    return jnp.where(jnp.isfinite(pn), snapped,
                     pn.astype(jnp.bfloat16).astype(jnp.float32))


def _tier_select(cwf, code, qs, ladder: str):
    """qdq_cast's tier math with a per-ROW fp8 scale column ``qs``."""
    if ladder == "tpu":
        low = (cwf * qs).astype(jnp.float8_e4m3fn).astype(jnp.float32) / qs
    else:
        low = cwf.astype(jnp.float16).astype(jnp.float32)
    mid = cwf.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(code == 0, low, jnp.where(code == 1, mid, cwf))


def _apply_kernel(scal_ref, meta_ref, g_ref, p_ref, m_ref, v_ref,
                  p_out, m_out, v_out, cp_out, pmax_ref,
                  *, spec: OptSpec, ladder: str, lanes: int,
                  sr: bool = False, interpret: bool = False):
    """(scalars, in SMEM) = [gscale, keep, c1, c2, sr_seed];
    ``v_ref``/``v_out`` are None for sgdm (momentum rides in ``m``)."""
    i = pl.program_id(0)
    gscale = scal_ref[0]
    keep = scal_ref[1] > 0.0
    g = g_ref[...].astype(jnp.float32) * gscale              # unscale + clip
    p = p_ref[...].astype(jnp.float32)

    if spec.kind == "sgdm":
        if spec.weight_decay:
            g = g + spec.weight_decay * p
        m2 = spec.momentum * m_ref[...] + g
        step = (spec.momentum * m2 + g) if spec.nesterov else m2
        v2 = None
    else:                                                    # adamw
        m2 = spec.b1 * m_ref[...] + (1.0 - spec.b1) * g
        v2 = spec.b2 * v_ref[...] + (1.0 - spec.b2) * jnp.square(g)
        step = (m2 / scal_ref[2]) / (jnp.sqrt(v2 / scal_ref[3]) + spec.eps)
        if spec.weight_decay:
            step = step + spec.weight_decay * p

    cols = _meta_cols(meta_ref)                              # (SLAB_M, 8)
    pn = p - cols[:, META_LR:META_LR + 1] * step
    pn = jnp.where(keep, pn, p)                              # non-finite skip
    m2 = jnp.where(keep, m2, m_ref[...])
    p_out[...] = pn
    m_out[...] = m2
    if v2 is not None:
        v_out[...] = jnp.where(keep, v2, v_ref[...])

    # ---- next-step compute copy: container cast + tier rounding ----------
    if sr:
        # stochastic container cast (bf16 only): kills the systematic
        # round-to-nearest EMA bias of repeated master->compute casts.
        # Tier rounding below (fp8) stays RTN — delayed scales assume it.
        seed = scal_ref[4].astype(jnp.int32)
        if interpret:
            cwf = _sr_to_bf16(pn, _sr_bits(i, seed.astype(jnp.uint32)))
        else:
            pltpu.prng_seed(seed, i)
            bits = pltpu.bitcast(
                pltpu.prng_random_bits((SLAB_M, SLAB_N)), jnp.uint32)
            cwf = pltpu.stochastic_round(
                pn, bits, target_dtype=jnp.bfloat16).astype(jnp.float32)
    else:
        cwf = pn.astype(cp_out.dtype).astype(jnp.float32)
    cp_out[...] = _tier_select(
        cwf, cols[:, META_CODE:META_CODE + 1],
        cols[:, META_QS:META_QS + 1], ladder).astype(cp_out.dtype)

    # per-layer absmax of the fresh compute copy (next step's fp8 scales)
    rmx = jnp.max(jnp.abs(cwf), axis=1, keepdims=True)
    mx_up = jnp.broadcast_to(
        _per_layer(_layer_mask(cols, lanes), rmx, jnp.max), (8, lanes))

    @pl.when(i == 0)
    def _init():
        pmax_ref[...] = mx_up

    @pl.when(i > 0)
    def _acc():
        pmax_ref[...] = jnp.maximum(pmax_ref[...], mx_up)


@functools.partial(jax.jit, static_argnames=("spec", "ladder", "cp_dtype",
                                             "num_layers", "interpret", "sr"))
def fused_apply(g_slab, p_slab, m_slab, v_slab, scalars, row_layer,
                lr_rows, code_rows, qs_rows, *, spec: OptSpec, ladder: str,
                cp_dtype, num_layers: int, interpret: bool = False,
                sr: bool = False):
    """Second (final) gradient read: optimizer + master write + cast.

    ``sr`` enables the stochastic container cast (effective only when
    ``cp_dtype`` is bfloat16 — the bitwise trick needs a truncation grid);
    the draw is seeded from ``scalars[4]``, a runtime value, so toggling
    the seed each step costs zero recompiles.

    Returns (p_new, m_new, v_new | None, compute_copy, p_amax(L,))."""
    lanes = _lanes(num_layers)
    nb = g_slab.shape[0] // SLAB_M
    adam = spec.kind == "adamw"
    sr = bool(sr) and jnp.dtype(cp_dtype) == jnp.dtype(jnp.bfloat16)
    if scalars.shape[0] == 4:                    # legacy (no-seed) callers
        scalars = jnp.concatenate([scalars, jnp.zeros((1,), scalars.dtype)])

    def kernel(scal, meta, g, p, m, *rest):
        if adam:
            v, p_o, m_o, v_o, cp_o, pmax = rest
        else:
            p_o, m_o, cp_o, pmax = rest
            v, v_o = None, None
        _apply_kernel(scal, meta, g, p, m, v, p_o, m_o, v_o, cp_o, pmax,
                      spec=spec, ladder=ladder, lanes=lanes,
                      sr=sr, interpret=interpret)

    slab_spec = pl.BlockSpec((SLAB_M, SLAB_N), lambda i: (i, 0))
    slab_sds = jax.ShapeDtypeStruct(p_slab.shape, jnp.float32)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),       # scalars
                _meta_block(), slab_spec, slab_spec, slab_spec]
    args = [scalars.astype(jnp.float32),
            row_meta(row_layer, lr_rows, code_rows, qs_rows),
            g_slab, p_slab, m_slab]
    out_specs = [slab_spec, slab_spec]
    out_shape = [slab_sds, slab_sds]
    if adam:
        in_specs.append(slab_spec)
        args.append(v_slab)
        out_specs.append(slab_spec)
        out_shape.append(slab_sds)
    out_specs += [slab_spec, _lane_block(lanes)]
    out_shape += [jax.ShapeDtypeStruct(p_slab.shape, cp_dtype),
                  jax.ShapeDtypeStruct((8, lanes), jnp.float32)]

    outs = pl.pallas_call(
        kernel, grid=(nb,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*args)
    if adam:
        p_new, m_new, v_new, cp, pmax = outs
    else:
        (p_new, m_new, cp, pmax), v_new = outs, None
    return p_new, m_new, v_new, cp, pmax[0, :num_layers]


# ===================================================== jnp-side helpers ===
def cast_scales(p_amax: jax.Array) -> jax.Array:
    """Per-layer fp8 cast scales from the carried param absmax (identical to
    qdq_cast's in-kernel derivation)."""
    return jnp.where(p_amax > 0, FP8_MAX / p_amax, 1.0)


def seed_compute(view: SlabView, params, codes: jax.Array, ladder: str,
                 cp_dtype, slab: bool = False) -> Dict[str, Any]:
    """Init/reseed the carried compute state: the compute copy the FIRST
    fused step's forward consumes, plus the per-layer param absmax table.
    One-off jnp pass (trainer init / restore only — every subsequent copy
    is emitted in-tile by the apply kernel). With ``slab=True`` the copy is
    kept in slab form (the resident path's carried representation)."""
    cw = view.pack(params, cp_dtype).astype(jnp.float32)
    rmx = jnp.max(jnp.abs(cw), axis=1)
    p_amax = jax.ops.segment_max(rmx, jnp.asarray(view.row_layer),
                                 num_segments=view.num_layers)
    p_amax = jnp.maximum(p_amax, 0.0)           # empty segments -> 0, not -inf
    code_r = view.gather_rows(codes).reshape(-1, 1)
    qs_r = view.gather_rows(cast_scales(p_amax)).reshape(-1, 1)
    cp = _tier_select(cw, code_r, qs_r, ladder).astype(cp_dtype)
    if slab:
        return {"slab": cp, "p_amax": p_amax}
    return {"tree": view.unpack(cp, like=params), "p_amax": p_amax}


def compute_sds(view: SlabView, params_sds, num_layers: int, cp_dtype,
                slab: bool = False):
    """abstract ``TrainState.compute`` for AOT lowering (launch.dryrun)."""
    if slab:
        return {"slab": jax.ShapeDtypeStruct((view.rows, SLAB_N), cp_dtype),
                "p_amax": jax.ShapeDtypeStruct((num_layers,), jnp.float32)}
    tree = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, cp_dtype if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), params_sds)
    return {"tree": tree,
            "p_amax": jax.ShapeDtypeStruct((num_layers,), jnp.float32)}
