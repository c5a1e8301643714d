"""Block-tiled flash attention: Pallas forward AND backward kernels that
run only the tiles the causal / sliding-window mask needs, with per-row
segment block skipping, plus a ragged per-slot-length decode kernel.

TPU-native tiling of the online-softmax algorithm: (BQ, D) query tiles and
(BK, D) key/value tiles resident in VMEM, fp32 accumulators in VMEM scratch
persisted across a run of grid steps that share one output block.

Tile tables. The causal and window masks are static, so which tiles hold
any unmasked pair is known at trace time: ``_tile_table`` lists exactly
those tiles, in the order a dense (q-block, k-block) walk would visit
them, and each kernel's grid walks that list on one flattened axis (the
megablox grouped-matmul idiom). The table reaches the kernels as a
scalar-prefetched int32 operand (``pltpu.PrefetchScalarGridSpec``) in
SMEM; every index_map and kernel body decodes the step's q-block, k-block
(and GQA head) from its entry, and two flag bits mark the first and last
step of each output block's run, where the accumulators are initialised
and written out. Tiles above the causal diagonal or outside the window are
never launched: no grid step, no DMA. Executed FLOPs are ~S^2/2 for causal
and ~S*W for windowed attention (unlike the chunked-jnp path, which
computes every pair and masks); non-causal attention walks the full
square. GQA is handled in the k/v index_map (q head h reads kv head
h // rep) so k/v are never materialized per q-head.

Layout. The public API takes the model's (B, S, H, D) tensors; the kernels
run on a heads-major (B, H, S, D) view, because Mosaic tiles the LAST TWO
block dims onto (sublane, lane) and requires them to be multiples of (8,
128) or the full array dims: a (BQ, D) tile satisfies that, a per-head
block of the (B, S, H, D) array (a 1 over H in the second-minor place)
does not. The per-row softmax residuals (logsumexp, and the backward's
D_i = sum_d dO_id * O_id) are stored lane-major as (B, H, 1, S), so the
backward kernels take scores TRANSPOSED — (BK, BQ) = k @ q^T — and
broadcast the residual rows across sublanes without any relayout.

Segment masking (packed multi-document rows): ``segments`` is a (B, S)
int32 array of NON-DECREASING per-row document ids; attention never crosses
a segment boundary. Positions are the within-segment arange, so within a
segment the global index difference EQUALS the positional difference — the
kernels keep masking on the global iota (causal/window) and add one
equality term (q_seg == k_seg). Because ids are sorted per row, a tile is
skippable exactly when its q/k segment-id ranges do not overlap — a
runtime predicate (``_segments_meet``), so it stays a ``pl.when`` inside
the steps of the tile table; forward and both backward kernels skip
identical blocks. The ids reach the kernels twice: as (B, S, 1) columns
and (B, 1, S) rows, so each kernel reads the orientation its score tile
needs.

The value head dim (Dv) is tiled independently of the q/k head dim (D):
MLA training (qk = nope+rope dim, v = v_head_dim) runs these kernels with
q/k (…, D) and v/o (…, Dv) BlockSpecs.

Training runs three kernels (FlashAttention-2 style; DESIGN.md §8, §14):

  * forward (``flash_attention_fwd``) — the inference forward plus one
    fp32 logsumexp residual, the ONLY extra tensor the backward needs
    beyond q/k/v/o (no (S, S) probabilities are ever materialized);
  * ``_dq_kernel`` — dQ, one q-tile accumulator swept over k-blocks
    (the forward's tile table, grid (B, H, T));
  * ``_dkv_kernel`` — dK and dV, one k-tile accumulator pair swept over the
    GQA head group x q-blocks (grid (B, K, T3), the table walking k-block,
    then q head of the group, then q-block), so grouped q-heads accumulate
    into their shared kv head without materializing per-q-head k/v
    gradients.

D_i is one fused XLA reduction ahead of them. All tables come from
``_block_needed`` and every tile is masked by ``_tile_mask``, so forward
and backward run exactly the same tiles. Each call records the grid steps
it launches against the dense walk's in a ``flash.grid`` span
(``repro.obs``) at trace time. ``kernels.ops`` binds fwd+bwd into one
differentiable op with ``jax.custom_vjp`` behind the dispatch gate.

``flash_decode`` is the serving-side ragged kernel: one query row per head
against a (B, L, K, D) cache plus a (B,) int32 length vector prefetched as
a scalar operand (``pltpu.PrefetchScalarGridSpec``), so the k-block loop
stops at ceil(len/BD) per row — the k/v index_map CLAMPS the block index to
the last needed block (skipped steps re-address the same tile, so no new
DMA is issued) and ``pl.when`` skips their compute. Each grid step reads
one (BD, K*D) slab of the cache — every kv head of BD slots, the cache's
own memory order — and scores all H query heads against it in one matmul
with a block-diagonal query (head h holds its vector in kv head h // rep's
lanes and zeros elsewhere). Decode HBM reads therefore scale with the
actual sequence length, not the cache capacity, and each cache byte is
read once per row rather than once per query head. Lengths are a traced
runtime operand: one compiled executable serves every slot-length pattern
(zero recompiles after serve warm()).

Shapes: q (B, S, H, D); k (B, S, K, D); v (B, S, K, Dv); H % K == 0;
S % BQ == S % BK == 0. VMEM at defaults (BQ=BK=256, D<=256 fp32): ~1.5 MiB
tiles + 0.5 MiB scratch (backward: ~2 MiB tiles + 1 MiB dk/dv scratch).
SMEM: one int32 per launched step of the table — causal S = 32k is 8,256
words (32 KiB) for the forward, the dK/dV table rep times that. A v5e core
has 1 MiB, which the compiler enforces: causal forwards fit to ~180k
tokens, dK/dV at rep 8 to ~64k; the longest configured sequence (the
32k prefill) is compiled in tests/test_chip_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

NEG_INF = -2.0e38
BQ = 256
BK = 256
#: candidate k-block sizes for the ragged decode kernel (largest dividing
#: the cache length wins; < 8 would break TPU sublane tiling -> no kernel)
DECODE_BLOCKS = (256, 128, 64, 32, 16, 8)

# (M, K) x (N, K) -> (M, N): contract the lane dims of both operands
_NT = (((1,), (1,)), ((), ()))
# (K, M) x (K, N) -> (M, N): contract the sublane dims of both operands
_TN = (((0,), (0,)), ((), ()))


def _heads_major(x):
    """(B, S, H, D) <-> (B, H, S, D)."""
    return jnp.swapaxes(x, 1, 2)


def _block_needed(q_start: int, k_start: int, causal: bool,
                  window: int) -> bool:
    """Does tile (q_start, k_start) contain ANY pair the causal / window
    mask keeps? Static: the tile tables of all three kernels are built from
    it, so forward and backward run identical tiles."""
    if causal and k_start > q_start + BQ - 1:
        return False
    if window and window > 0 and k_start + BK - 1 < q_start - (window - 1):
        return False
    return True


def _segments_meet(sq, sk):
    """Runtime part of the skip: do the tile's q and k segment-id ranges
    overlap? Ids are non-decreasing along the sequence (either orientation),
    so a tile whose ranges do not overlap is fully cross-document. Without
    segments every launched tile runs (``pl.when(True)`` calls straight
    through)."""
    return (jnp.max(sq) >= jnp.min(sk)) & (jnp.min(sq) <= jnp.max(sk))


# Tile-table entry, one int32 per launched grid step: q-block in bits 0-11,
# k-block in 12-23, first / last step of its output block's run in 24 / 25,
# and (dK/dV walk) the q head within the GQA group in 26-31.
_FIELD = 12
_FIELD_MASK = (1 << _FIELD) - 1
_FIRST = 1 << 24
_LAST = 1 << 25
_R_SHIFT = 26
_R_MAX = 1 << (32 - _R_SHIFT)


def _tile_table(nq: int, nk: int, causal: bool, window: int,
                rep: int = 0) -> np.ndarray:
    """The tiles ``_block_needed`` keeps, in dense-walk order, as packed
    int32 entries. ``rep == 0``: the forward / dQ walk — for each q-block,
    its k-blocks ascending (output block: the q-block). ``rep > 0``: the
    dK/dV walk — for each k-block, each of the ``rep`` q heads of the GQA
    group, its q-blocks ascending (output block: the k-block). With q and
    k of one length every output block has a needed tile (the one holding
    its diagonal), so each is initialised and written once; a block with
    none would be left unwritten, and is refused."""
    if max(nq, nk) > _FIELD_MASK + 1 or rep > _R_MAX:
        raise ValueError(f"tile table fields overflow: {nq=} {nk=} {rep=}")
    need = [[_block_needed(qi * BQ, ki * BK, causal, window)
             for ki in range(nk)] for qi in range(nq)]
    if rep:
        runs = [[qi | ki << _FIELD | r << _R_SHIFT
                 for r in range(rep) for qi in range(nq) if need[qi][ki]]
                for ki in range(nk)]
    else:
        runs = [[qi | ki << _FIELD for ki in range(nk) if need[qi][ki]]
                for qi in range(nq)]
    out = []
    for run in runs:
        if not run:
            raise ValueError(f"an output block has no needed tile: {nq=} "
                             f"{nk=} {causal=} {window=}")
        run[0] |= _FIRST
        run[-1] |= _LAST
        out += run
    return np.asarray(out, np.uint32).view(np.int32)


def _qi(e):
    return e & _FIELD_MASK


def _ki(e):
    return (e >> _FIELD) & _FIELD_MASK


def _r(e):
    return jax.lax.shift_right_logical(e, jnp.int32(_R_SHIFT))


def _first(e):
    return (e & _FIRST) != 0


def _last(e):
    return (e & _LAST) != 0


def _note_grid(kernel: str, launched: int, dense: int) -> None:
    """Trace-time record of the grid steps one call launches against the
    dense walk's (read by the benchmark's ``train.flash_grid_share``)."""
    with obs.span("flash.grid", kernel=kernel, launched=launched,
                  dense=dense):
        pass


def _tile_mask(q_start, k_start, causal: bool, window: int, sq=None, sk=None,
               transposed: bool = False):
    """Bool mask of valid pairs inside one tile: (BQ, BK), or (BK, BQ) with
    ``transposed`` (then ``sk`` is a (BK, 1) column and ``sq`` a (1, BQ)
    row; otherwise ``sq`` is the column and ``sk`` the row). With segments,
    positions are the within-segment arange, so the global-iota causal and
    window terms are exact inside a segment and the segment equality term
    kills every cross-document pair."""
    shape = (BK, BQ) if transposed else (BQ, BK)
    qd, kd = (1, 0) if transposed else (0, 1)
    qp = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, qd)
    kp = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, kd)
    d = qp - kp
    ok = jnp.ones(shape, jnp.bool_)
    if causal:
        ok = ok & (d >= 0)
    if window and window > 0:
        ok = ok & (d < window)
    if sq is not None:
        ok = ok & (sq == sk)
    return ok


def _seg_views(segments):
    """(B, S) ids -> ((B, S, 1) columns, (B, 1, S) rows)."""
    seg = segments.astype(jnp.int32)
    B, S = seg.shape
    return seg.reshape(B, S, 1), seg.reshape(B, 1, S)


# ================================================================ forward ==
def _fwd_kernel(tbl_ref, *refs, causal: bool, window: int, scale: float,
                seg: bool, with_lse: bool):
    q_ref, k_ref, v_ref = refs[:3]
    rest = refs[3:]
    sq_ref = sk_ref = lse_ref = None
    if seg:
        sq_ref, sk_ref, *rest = rest
    o_ref, *rest = rest
    if with_lse:
        lse_ref, *rest = rest
    acc_ref, m_ref, l_ref = rest
    e = tbl_ref[pl.program_id(2)]

    @pl.when(_first(e))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = _qi(e) * BQ
    k_start = _ki(e) * BK
    sq = None if sq_ref is None else sq_ref[...]              # (BQ, 1)
    sk = None if sk_ref is None else sk_ref[...]              # (1, BK)

    @pl.when(sq is None or _segments_meet(sq, sk))
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale             # (BQ, D)
        k = k_ref[...].astype(jnp.float32)                     # (BK, D)
        v = v_ref[...].astype(jnp.float32)                     # (BK, Dv)
        s = jax.lax.dot_general(q, k, _NT)                     # (BQ, BK)
        s = jnp.where(_tile_mask(q_start, k_start, causal, window, sq, sk),
                      s, NEG_INF)
        m_prev = m_ref[...]                                    # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(_last(e))
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp over the row's valid scores: the one residual the
            # backward rebuilds p from (p = exp(s - lse)). Stored lane-major:
            # the (BQ, 1) column goes through one (BQ, 128) transpose.
            lse = jnp.broadcast_to(m_ref[...] + jnp.log(l), (BQ, 128))
            lse_ref[...] = jnp.transpose(lse)[0:1, :]


def _fwd_call(q, k, v, segments, *, causal, window, scale, interpret,
              with_lse):
    B, S, H, D = q.shape
    K = k.shape[2]
    Dv = v.shape[-1]
    rep = H // K
    assert S % BQ == 0 and S % BK == 0, (S, BQ, BK)
    if scale is None:
        scale = D ** -0.5
    nq, nk = S // BQ, S // BK
    window = int(window or 0)
    tbl = _tile_table(nq, nk, causal, window)
    _note_grid("fwd", B * H * tbl.size, B * H * nq * nk)
    seg = segments is not None
    kern = functools.partial(
        _fwd_kernel, causal=causal, window=window, scale=float(scale),
        seg=seg, with_lse=with_lse)
    in_specs = [
        pl.BlockSpec((None, None, BQ, D),
                     lambda b, h, t, tb: (b, h, _qi(tb[t]), 0)),
        pl.BlockSpec((None, None, BK, D),
                     lambda b, h, t, tb: (b, h // rep, _ki(tb[t]), 0)),
        pl.BlockSpec((None, None, BK, Dv),
                     lambda b, h, t, tb: (b, h // rep, _ki(tb[t]), 0)),
    ]
    args = [_heads_major(q), _heads_major(k), _heads_major(v)]
    if seg:
        col, row = _seg_views(segments)
        in_specs += [
            pl.BlockSpec((None, BQ, 1),
                         lambda b, h, t, tb: (b, _qi(tb[t]), 0)),
            pl.BlockSpec((None, 1, BK),
                         lambda b, h, t, tb: (b, 0, _ki(tb[t])))]
        args += [col, row]
    out_shape = [jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype)]
    out_specs = [pl.BlockSpec((None, None, BQ, Dv),
                              lambda b, h, t, tb: (b, h, _qi(tb[t]), 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32))
        out_specs.append(pl.BlockSpec(
            (None, None, 1, BQ), lambda b, h, t, tb: (b, h, 0, _qi(tb[t]))))
    res = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, tbl.size),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((BQ, Dv), jnp.float32),
                pltpu.VMEM((BQ, 1), jnp.float32),
                pltpu.VMEM((BQ, 1), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(tbl), *args)
    o = _heads_major(res[0])
    return (o, res[1]) if with_lse else (o,)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def flash_attention(q, k, v, segments=None, *, causal: bool = True,
                    window: int = 0, scale: float = None,
                    interpret: bool = False):
    """Inference/primal forward: no residual write."""
    return _fwd_call(q, k, v, segments, causal=causal, window=window,
                     scale=scale, interpret=interpret, with_lse=False)[0]


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def flash_attention_fwd(q, k, v, segments=None, *, causal: bool = True,
                        window: int = 0, scale: float = None,
                        interpret: bool = False):
    """Training forward: returns (o, lse) with lse (B, H, 1, S) fp32."""
    return _fwd_call(q, k, v, segments, causal=causal, window=window,
                     scale=scale, interpret=interpret, with_lse=True)


# =============================================================== backward ==
def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq, sk,
              q_start, k_start, *, causal, window, scale):
    """Shared transposed-score tile of both backward kernels: returns the
    pre-scaled q, k, and (pT, dsT), each (BK, BQ)."""
    q = q_ref[...].astype(jnp.float32) * scale                 # (BQ, D)
    k = k_ref[...].astype(jnp.float32)                         # (BK, D)
    v = v_ref[...].astype(jnp.float32)                         # (BK, Dv)
    do = do_ref[...].astype(jnp.float32)                       # (BQ, Dv)
    st = jax.lax.dot_general(k, q, _NT)                        # (BK, BQ)
    st = jnp.where(_tile_mask(q_start, k_start, causal, window, sq, sk,
                              transposed=True), st, NEG_INF)
    pt = jnp.exp(st - lse_ref[...])                 # masked pairs -> 0
    dpt = jax.lax.dot_general(v, do, _NT)                      # (BK, BQ)
    dst = pt * (dpt - delta_ref[...])
    return q, k, do, pt, dst


def _dq_kernel(tbl_ref, *refs, causal: bool, window: int, scale: float,
               seg: bool):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    sq = sk = None
    if seg:
        sq_ref, sk_ref, *rest = rest
        sq, sk = sq_ref[...], sk_ref[...]                      # (1,BQ),(BK,1)
    dq_ref, acc_ref = rest
    e = tbl_ref[pl.program_id(2)]

    @pl.when(_first(e))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = _qi(e) * BQ
    k_start = _ki(e) * BK

    @pl.when(sq is None or _segments_meet(sq, sk))
    def _compute():
        _, k, _, _, dst = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq, sk,
            q_start, k_start, causal=causal, window=window, scale=scale)
        acc_ref[...] += jax.lax.dot_general(dst, k, _TN)       # (BQ, D)

    @pl.when(_last(e))
    def _finalize():
        # s was taken against scale*q, so d/dq carries one more factor
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(tbl_ref, *refs, causal: bool, window: int, scale: float,
                seg: bool):
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    rest = refs[6:]
    sq = sk = None
    if seg:
        sq_ref, sk_ref, *rest = rest
        sq, sk = sq_ref[...], sk_ref[...]                      # (1,BQ),(BK,1)
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    e = tbl_ref[pl.program_id(2)]

    @pl.when(_first(e))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = _qi(e) * BQ
    k_start = _ki(e) * BK

    @pl.when(sq is None or _segments_meet(sq, sk))
    def _compute():
        q, _, do, pt, dst = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq, sk,
            q_start, k_start, causal=causal, window=window, scale=scale)
        dv_acc[...] += jnp.dot(pt, do)                         # (BK, Dv)
        dk_acc[...] += jnp.dot(dst, q)          # q pre-scaled: dk done

    @pl.when(_last(e))
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "interpret"))
def flash_attention_bwd(q, k, v, o, lse, do, segments=None, *,
                        causal: bool = True, window: int = 0,
                        scale: float = None, interpret: bool = False):
    """(dq, dk, dv) from the saved (q, k, v, o, lse) residuals."""
    B, S, H, D = q.shape
    K = k.shape[2]
    Dv = v.shape[-1]
    rep = H // K
    assert S % BQ == 0 and S % BK == 0, (S, BQ, BK)
    if scale is None:
        scale = D ** -0.5
    nq, nk = S // BQ, S // BK
    window = int(window or 0)
    kw = dict(causal=causal, window=window, scale=float(scale),
              seg=segments is not None)
    # D_i = sum_d dO_id * O_id: one fused XLA reduction, lane-major like lse
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2).reshape(B, H, 1, S)
    qh, kh, vh, doh = (_heads_major(x) for x in (q, k, v, do))
    seg_args = []
    if segments is not None:
        col, row = _seg_views(segments)
        seg_args = [row, col]

    # dq: the forward's walk, one (BQ, D) accumulator per q-block
    tbl = _tile_table(nq, nk, causal, window)
    _note_grid("dq", B * H * tbl.size, B * H * nq * nk)
    qmap = lambda b, h, t, tb: (b, h, _qi(tb[t]), 0)
    kmap = lambda b, h, t, tb: (b, h // rep, _ki(tb[t]), 0)
    rowmap = lambda b, h, t, tb: (b, h, 0, _qi(tb[t]))
    dq_in_specs = [
        pl.BlockSpec((None, None, BQ, D), qmap),
        pl.BlockSpec((None, None, BK, D), kmap),
        pl.BlockSpec((None, None, BK, Dv), kmap),
        pl.BlockSpec((None, None, BQ, Dv), qmap),
        pl.BlockSpec((None, None, 1, BQ), rowmap),
        pl.BlockSpec((None, None, 1, BQ), rowmap),
    ]
    if seg_args:
        dq_in_specs += [
            pl.BlockSpec((None, 1, BQ),
                         lambda b, h, t, tb: (b, 0, _qi(tb[t]))),
            pl.BlockSpec((None, BK, 1),
                         lambda b, h, t, tb: (b, _ki(tb[t]), 0))]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, tbl.size),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((None, None, BQ, D), qmap),
            scratch_shapes=[pltpu.VMEM((BQ, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(tbl), qh, kh, vh, doh, lse, delta, *seg_args)

    # dk/dv: one (BK, D) accumulator pair per kv head, swept over the GQA
    # head group (r) and all needed q-blocks — grouped q-heads reduce into
    # the shared kv head inside VMEM, never through HBM
    tbl3 = _tile_table(nq, nk, causal, window, rep=rep)
    _note_grid("dkv", B * K * tbl3.size, B * K * nk * rep * nq)
    qmap = lambda b, g, t, tb: (b, g * rep + _r(tb[t]), _qi(tb[t]), 0)
    kmap = lambda b, g, t, tb: (b, g, _ki(tb[t]), 0)
    rowmap = lambda b, g, t, tb: (b, g * rep + _r(tb[t]), 0, _qi(tb[t]))
    dkv_in_specs = [
        pl.BlockSpec((None, None, BQ, D), qmap),
        pl.BlockSpec((None, None, BK, D), kmap),
        pl.BlockSpec((None, None, BK, Dv), kmap),
        pl.BlockSpec((None, None, BQ, Dv), qmap),
        pl.BlockSpec((None, None, 1, BQ), rowmap),
        pl.BlockSpec((None, None, 1, BQ), rowmap),
    ]
    if seg_args:
        dkv_in_specs += [
            pl.BlockSpec((None, 1, BQ),
                         lambda b, g, t, tb: (b, 0, _qi(tb[t]))),
            pl.BlockSpec((None, BK, 1),
                         lambda b, g, t, tb: (b, _ki(tb[t]), 0))]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, tbl3.size),
            in_specs=dkv_in_specs,
            out_specs=[pl.BlockSpec((None, None, BK, D), kmap),
                       pl.BlockSpec((None, None, BK, Dv), kmap)],
            scratch_shapes=[
                pltpu.VMEM((BK, D), jnp.float32),
                pltpu.VMEM((BK, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, K, S, D), k.dtype),
            jax.ShapeDtypeStruct((B, K, S, Dv), v.dtype),
        ],
        interpret=interpret,
    )(jnp.asarray(tbl3), qh, kh, vh, doh, lse, delta, *seg_args)
    return _heads_major(dq), _heads_major(dk), _heads_major(dv)


# ========================================================== ragged decode ==
def decode_block(L: int):
    """k-block size for a cache of length ``L`` (None -> no ragged kernel
    for this geometry; callers fall back). Prefers the largest supported
    block that still gives the ragged loop >= 4 steps — a single whole-cache
    block would read capacity bytes regardless of the live length, defeating
    the per-slot-length skipping — falling back to the largest divisor for
    short caches."""
    largest = None
    for bd in DECODE_BLOCKS:
        if L % bd == 0:
            if largest is None:
                largest = bd
            if 4 * bd <= L:
                return bd
    return largest


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, bd: int, nk: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    # blocks at/after ceil(len/bd) are fully masked: their index_map clamps
    # to the last needed tile (no new DMA) and compute is skipped entirely
    @pl.when(ki * bd < length)
    def _compute():
        q = q_ref[...].astype(jnp.float32)             # (Hp, K*D), scaled
        k = k_ref[...].astype(jnp.float32)             # (bd, K*D)
        v = v_ref[...].astype(jnp.float32)             # (bd, K*Dv)
        s = jax.lax.dot_general(q, k, _NT)             # (Hp, bd)
        slot = ki * bd + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode(q, k, v, lengths, *, scale: float = None,
                 interpret: bool = False):
    """Ragged single-token decode: q (B, 1, H, D) against a (B, L, K, D)
    k / (B, L, K, Dv) v cache; row b attends slots [0, lengths[b]).

    ``lengths`` is a (B,) int32 RUNTIME vector (scalar-prefetched), so the
    executable is shape-stable across slot-length patterns; the per-row
    k-block loop stops at ceil(lengths[b] / BD). Requires the cache to hold
    positions contiguously from slot 0 (full-length caches — no ring wrap),
    which ``nn.attention.gqa_decode`` guarantees for unwindowed blocks."""
    B, Sq, H, D = q.shape
    assert Sq == 1, q.shape
    L, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // K
    bd = decode_block(L)
    assert bd is not None, (L, DECODE_BLOCKS)
    nk = L // bd
    if scale is None:
        scale = D ** -0.5
    Hp = -(-H // 8) * 8
    # block-diagonal query: head h's (pre-scaled) vector in the lanes of kv
    # head h // rep, zeros in every other kv head's lanes and pad rows
    own = jnp.arange(Hp)[:, None] // rep == jnp.arange(K)[None, :]
    qh = jnp.pad(q[:, 0].astype(jnp.float32) * scale,
                 ((0, 0), (0, Hp - H), (0, 0)))
    qbd = jnp.where(own[None, :, :, None], qh[:, :, None, :], 0.0)
    qbd = qbd.reshape(B, Hp, K * D)

    def kv_map(b, ki, len_ref):
        last = jnp.maximum((len_ref[b] + bd - 1) // bd - 1, 0)
        return (b, jnp.minimum(ki, last), 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, bd=bd, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((None, Hp, K * D),
                             lambda b, ki, len_ref: (b, 0, 0)),
                pl.BlockSpec((None, bd, K * D), kv_map),
                pl.BlockSpec((None, bd, K * Dv), kv_map),
            ],
            out_specs=pl.BlockSpec((None, Hp, K * Dv),
                                   lambda b, ki, len_ref: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hp, K * Dv), jnp.float32),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hp, K * Dv), jnp.float32),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qbd, k.reshape(B, L, K * D),
      v.reshape(B, L, K * Dv))
    # keep each head's own kv-head block of the output lanes
    out = out[:, :H].reshape(B, H, K, Dv)
    out = jnp.take_along_axis(
        out, (jnp.arange(H) // rep)[None, :, None, None], axis=2)[:, :, 0]
    return out[:, None].astype(q.dtype)
