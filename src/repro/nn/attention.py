"""Attention variants: GQA (+rope/m-rope, sliding window), MLA, cross-attn.

Three execution paths:
  * ``naive``   — materializes (Sq, Sk) scores; reference, tests, decode.
  * ``chunked`` — flash-style online-softmax double scan over (q, k) chunks;
                  pure jnp, lowers on any backend, O(q_chunk*k_chunk) score
                  memory. The fallback when the kernel gate fails.
  * Pallas flash kernel (repro.kernels.flash_attention) — TPU target,
    selected with impl="flash" (the default for LM/enc-dec training
    configs; validated in interpret mode in tests). Differentiable
    end-to-end: kernels.ops binds the Pallas backward kernels with
    jax.custom_vjp, so training runs the kernel in BOTH directions with
    only the (B, H, 1, S) logsumexp residual saved — no O(S*S/chunk)
    score residuals. Packed multi-document batches run the kernel too:
    ``segments`` (per-row non-decreasing int32 document ids) feed the
    kernels' segment block masking when the constructor declares the
    positions segment-standard (``segment_positions`` below), and MLA's
    split qk/v dims use the kernels' independent Dv tiling. The remaining
    out-of-gate configurations (ragged offsets without segment ids, traced
    windows, non-block-divisible lengths) fall back to chunked/naive —
    which also honor ``segments`` — and JAX differentiates them natively.

Decode paths use full or ring (sliding-window) KV caches; MLA decode uses the
compressed-cache *absorbed* formulation (cache holds only (c_kv, k_rope)).
GQA decode over unwindowed full-length caches dispatches the ragged
per-slot-length Pallas kernel (kernels.flash_attention.flash_decode): HBM
reads scale with each row's actual length, not the cache capacity.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn.layers import (apply_mrope, apply_rope, dense, dense_init,
                             rmsnorm, rmsnorm_init)

NEG_INF = -2.0e38

# ----------------------------------------------- standard-positions hint ---
# The Pallas flash kernel hard-codes the standard arange mask, so its
# dispatcher must PROVE positions are standard — impossible from inside a
# jit trace, where even arange-built arrays are tracers. The call site that
# CONSTRUCTS the positions (lm_hidden/encdec: batch carried none -> built
# from arange) has that knowledge statically; it declares it here so
# impl="flash" still reaches the kernel under jit. Same thread-local
# pattern as launch.sharding.activation_mesh.
_STD_POS = threading.local()


@contextlib.contextmanager
def std_positions(flag: bool = True):
    """Declare that positions flowing into ``attention()`` below are the
    standard broadcast arange (train / prefill with no packed batch)."""
    prev = getattr(_STD_POS, "flag", False)
    _STD_POS.flag = bool(flag)
    try:
        yield
    finally:
        _STD_POS.flag = prev


# Packed-batch analog of std_positions: the kernels' segment masking keeps
# causal/window terms on the global iota, which is only exact when positions
# restart from 0 at every segment boundary (the within-segment arange). The
# constructor that BUILDS positions from segment ids (packed_positions
# below, used by models.lm/encdec) declares that contract here.
_SEG_POS = threading.local()


@contextlib.contextmanager
def segment_positions(flag: bool = True):
    """Declare that positions flowing into ``attention()`` below are the
    within-segment arange of the ``segments`` array passed alongside them
    (packed multi-document batch built by ``packed_positions``)."""
    prev = getattr(_SEG_POS, "flag", False)
    _SEG_POS.flag = bool(flag)
    try:
        yield
    finally:
        _SEG_POS.flag = prev


def packed_positions(segments: jax.Array) -> jax.Array:
    """Within-segment arange for a packed batch: segments (B, S) int32 with
    NON-DECREASING per-row document ids -> positions restarting at 0 on
    every document boundary ([0,0,1,1,1] -> [0,1,0,1,2])."""
    B, S = segments.shape
    idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    is_start = jnp.concatenate(
        [jnp.ones((B, 1), bool), segments[:, 1:] != segments[:, :-1]], axis=1)
    start = jax.lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return idx - start


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    mrope_sections: Optional[Tuple[int, ...]] = None
    qk_norm: bool = False          # gemma3-style RMSNorm on q/k head vectors
    causal: bool = True
    impl: str = "chunked"          # "naive" | "chunked" | "flash"
    q_chunk: int = 512
    k_chunk: int = 512
    softmax_scale: Optional[float] = None

    @property
    def scale(self) -> float:
        return (self.softmax_scale if self.softmax_scale is not None
                else self.head_dim ** -0.5)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    num_heads: int
    q_lora_rank: Optional[int]     # None -> direct q projection (v2-lite)
    kv_lora_rank: int
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    impl: str = "chunked"
    q_chunk: int = 512
    k_chunk: int = 512

    @property
    def scale(self) -> float:
        return (self.qk_nope_dim + self.qk_rope_dim) ** -0.5


# =========================================================== mask helpers ==
def decode_index(index, batch: int) -> jax.Array:
    """Normalize a decode index to per-request positions (B,) int32.

    ``index`` may be a scalar (every request at the same position — the
    dry-run serve shapes) or a (B,) vector (continuous batching: each slot
    carries its own offset)."""
    return jnp.broadcast_to(jnp.asarray(index, jnp.int32), (batch,))



def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool, window,
               q_seg=None, k_seg=None) -> jax.Array:
    """Additive bias (0 / NEG_INF). q_pos: (B, Sq), k_pos: (B, Sk) -> (B, Sq, Sk).

    ``window`` may be a traced int32 scalar; <= 0 means global attention.
    Cache slots with position < 0 are treated as empty (always masked).
    ``q_seg``/``k_seg`` (packed batches) additionally mask every
    cross-document pair: attention never crosses a segment boundary.
    """
    d = q_pos[:, :, None] - k_pos[:, None, :]
    ok = k_pos[:, None, :] >= 0
    if causal:
        ok = ok & (d >= 0)
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        ok = ok & jnp.where(w > 0, d < w, True)
    if q_seg is not None:
        ok = ok & (q_seg[:, :, None] == k_seg[:, None, :])
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ======================================================= core attention ====
def _naive_attention(q, k, v, q_pos, k_pos, causal, window, scale,
                     q_seg=None, k_seg=None):
    """q: (B, Sq, H, D); k: (B, Sk, K, D); v: (B, Sk, K, Dv) -> (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    rep = H // K
    qr = q.reshape(B, Sq, K, rep, D).astype(jnp.float32) * scale
    scores = jnp.einsum("bqkrd,bskd->bqkrs", qr, k.astype(jnp.float32))
    bias = _mask_bias(q_pos, k_pos, causal, window, q_seg, k_seg)  # (B,Sq,Sk)
    scores = scores + bias[:, :, None, None, :]
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkrs,bskd->bqkrd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _chunked_attention(q, k, v, q_pos, k_pos, causal, window, scale,
                       q_chunk, k_chunk, q_seg=None, k_seg=None):
    """Flash-style online softmax; outer scan over q chunks, inner over k.

    Sliding-window optimization: when ``window`` is a STATIC python int and
    the attention is causal self-attention (Sq == Sk), each q chunk only
    reads a static-size band of k/v ending at its own diagonal — executed
    FLOPs drop from O(S^2) to O(S * (window + q_chunk)) on every backend
    (the masked-but-computed chunks are not even loaded). Traced windows
    fall back to the full masked sweep. Segment ids (packed batches) ride
    along with the positions; the band optimization stays sound because
    segment masking only ever REMOVES pairs from the causal/window band.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // K
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0, (Sq, q_chunk, Sk, k_chunk)
    nq, nk = Sq // q_chunk, Sk // k_chunk
    seg = q_seg is not None

    band = None
    if (isinstance(window, int) and window > 0 and causal and Sq == Sk):
        band_len = -(-(window - 1 + q_chunk) // k_chunk) * k_chunk
        if band_len < Sk:
            band = band_len

    qr = (q.reshape(B, nq, q_chunk, K, rep, D).astype(jnp.float32) * scale)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qpr = q_pos.reshape(B, nq, q_chunk)
    qsr = q_seg.reshape(B, nq, q_chunk) if seg else None

    def inner(qc, qp, qs, ks, vs, kps, kss, n_chunks):
        def k_step(carry, ki):
            acc, m, l = carry
            kc, vc, kp = ki[0], ki[1], ki[2]
            ksg = ki[3] if seg else None
            s = jnp.einsum("bqkrd,bskd->bqkrs", qc, kc)  # (B,qc,K,rep,kc)
            s = s + _mask_bias(qp, kp, causal, window,
                               qs, ksg)[:, :, None, None, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bqkrs,bskd->bqkrd", p, vc)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, q_chunk, K, rep, Dv), jnp.float32)
        m0 = jnp.full((B, q_chunk, K, rep), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, K, rep), jnp.float32)
        kr = ks.reshape(B, n_chunks, k_chunk, K, D)
        vr = vs.reshape(B, n_chunks, k_chunk, K, Dv)
        kpr = kps.reshape(B, n_chunks, k_chunk)
        xs = [kr.swapaxes(0, 1), vr.swapaxes(0, 1), kpr.swapaxes(0, 1)]
        if seg:
            xs.append(kss.reshape(B, n_chunks, k_chunk).swapaxes(0, 1))
        (acc, m, l), _ = jax.lax.scan(k_step, (acc0, m0, l0), tuple(xs))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    if band is None:
        def q_step(_, xs):
            qc, qp = xs[0], xs[1]
            qs = xs[2] if seg else None
            return None, inner(qc, qp, qs, kf, vf, k_pos, k_seg, nk)

        qxs = [qr.swapaxes(0, 1), qpr.swapaxes(0, 1)]
        if seg:
            qxs.append(qsr.swapaxes(0, 1))
        _, outs = jax.lax.scan(q_step, None, tuple(qxs))
    else:
        def q_step(_, xs):
            qc, qp, qi = xs[0], xs[1], xs[2]
            qs = xs[3] if seg else None
            start = jnp.clip(qi * q_chunk + q_chunk - band, 0, Sk - band)
            ks = jax.lax.dynamic_slice(kf, (0, start, 0, 0), (B, band, K, D))
            vs = jax.lax.dynamic_slice(vf, (0, start, 0, 0), (B, band, K, Dv))
            kps = jax.lax.dynamic_slice(k_pos, (0, start), (B, band))
            kss = (jax.lax.dynamic_slice(k_seg, (0, start), (B, band))
                   if seg else None)
            return None, inner(qc, qp, qs, ks, vs, kps, kss, band // k_chunk)

        qxs = [qr.swapaxes(0, 1), qpr.swapaxes(0, 1),
               jnp.arange(nq, dtype=jnp.int32)]
        if seg:
            qxs.append(qsr.swapaxes(0, 1))
        _, outs = jax.lax.scan(q_step, None, tuple(qxs))
    # outs: (nq, B, q_chunk, K, rep, Dv)
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


def attention(q, k, v, q_pos, k_pos, *, causal, window, scale,
              impl="chunked", q_chunk=512, k_chunk=512, segments=None):
    if impl == "flash":
        # TPU Pallas kernel path (repro.kernels.ops); falls back to chunked/
        # naive when the kernel does not support the configuration. Dropping
        # the position arrays is only sound for self-attention positions the
        # constructor DECLARED standard (std_positions above) or declared the
        # within-segment arange of ``segments`` (segment_positions above).
        from repro.kernels import ops as kops
        hinted = q_pos is k_pos and (
            getattr(_SEG_POS, "flag", False) if segments is not None
            else getattr(_STD_POS, "flag", False))
        return kops.flash_attention(q, k, v,
                                    None if hinted else q_pos,
                                    None if hinted else k_pos,
                                    segments=segments,
                                    causal=causal, window=window, scale=scale)
    if impl == "chunked" and q.shape[1] % q_chunk == 0 and k.shape[1] % k_chunk == 0 \
            and q.shape[1] >= q_chunk and k.shape[1] >= k_chunk:
        return _chunked_attention(q, k, v, q_pos, k_pos, causal, window,
                                  scale, q_chunk, k_chunk,
                                  q_seg=segments, k_seg=segments)
    return _naive_attention(q, k, v, q_pos, k_pos, causal, window, scale,
                            q_seg=segments, k_seg=segments)


# ================================================================= GQA ======
# Projections are kept 3-D (d_model, heads, head_dim) so tensor parallelism
# shards the *head* axis directly — a 2-D (d, H*D) kernel sharded on the
# flattened dim forces XLA to re-shard at every (H, D) reshape when H is not
# a multiple of the mesh axis (all-gathers inside the layer scan).
def _proj_init(key, dm, heads, hd, name):
    import math as _m
    from repro.nn.module import param as _param
    return {"kernel": _param(key, (dm, heads, hd), ("embed", name, None),
                             "normal", 1.0 / _m.sqrt(dm))}


def _out_init(key, heads, hd, dm):
    import math as _m
    from repro.nn.module import param as _param
    return {"kernel": _param(key, (heads, hd, dm), ("heads", None, "embed"),
                             "normal", 1.0 / _m.sqrt(heads * hd))}


def proj(p, x):
    """(B,S,d) @ (d,H,D) -> (B,S,H,D)."""
    return jnp.einsum("bsd,dhk->bshk", x, p["kernel"].astype(x.dtype))


def out_proj(p, y):
    """(B,S,H,D) @ (H,D,d) -> (B,S,d)."""
    return jnp.einsum("bshk,hkd->bsd", y, p["kernel"].astype(y.dtype))


def gqa_init(key: jax.Array, cfg: AttnConfig):
    ks = jax.random.split(key, 6)
    H, K, D, dm = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": _proj_init(ks[0], dm, H, D, "heads"),
        "wk": _proj_init(ks[1], dm, K, D, "kv"),
        "wv": _proj_init(ks[2], dm, K, D, "kv"),
        "wo": _out_init(ks[3], H, D, dm),
    }
    if cfg.qk_norm:
        p["qnorm"] = rmsnorm_init(ks[4], D)
        p["knorm"] = rmsnorm_init(ks[5], D)
    return p


def _gqa_qkv(p, x, q_pos, cfg: AttnConfig, mrope_positions=None):
    B, S, _ = x.shape
    H, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = proj(p["wq"], x)
    k = proj(p["wk"], x)
    v = proj(p["wv"], x)
    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q)
        k = rmsnorm(p["knorm"], k)
    if cfg.mrope_sections is not None and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    return q, k, v


def gqa_fwd(p, x, q_pos, cfg: AttnConfig, window=None, mrope_positions=None,
            return_cache=False, segments=None):
    """Self-attention over a full sequence (train / prefill).

    x: (B, S, d_model); q_pos: (B, S) int32. Returns y (and KV cache when
    ``return_cache``: rope-applied keys, values, and slot positions).
    ``segments`` (B, S) int32 marks packed multi-document rows; attention
    never crosses a document boundary.
    """
    B, S, _ = x.shape
    q, k, v = _gqa_qkv(p, x, q_pos, cfg, mrope_positions)
    out = attention(q, k, v, q_pos, q_pos, causal=cfg.causal, window=window,
                    scale=cfg.scale, impl=cfg.impl, q_chunk=cfg.q_chunk,
                    k_chunk=cfg.k_chunk, segments=segments)
    y = out_proj(p["wo"], out)
    if return_cache:
        return y, {"k": k, "v": v, "pos": q_pos}
    return y


def gqa_init_cache(cfg: AttnConfig, batch: int, length: int, dtype=jnp.bfloat16):
    K, D = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, length, K, D), dtype),
            "v": jnp.zeros((batch, length, K, D), dtype),
            "pos": jnp.full((batch, length), -1, jnp.int32)}


def gqa_decode(p, x, cache, index, cfg: AttnConfig, window=None,
               mrope_positions=None):
    """One decode step. x: (B, 1, d_model); index: scalar int32 OR a (B,)
    vector of per-request positions (continuous batching — each slot advances
    independently; the cache update is a per-row scatter).

    The cache ring-buffers when its length < the attended context (sliding
    window); with a full-length cache the slot is the absolute position.
    """
    B = x.shape[0]
    L = cache["k"].shape[1]
    idx = decode_index(index, B)
    pos = idx[:, None]
    q, k_new, v_new = _gqa_qkv(p, x, pos, cfg, mrope_positions)
    slot = idx % L
    rows = jnp.arange(B)
    k = cache["k"].at[rows, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[rows, slot].set(v_new[:, 0].astype(cache["v"].dtype))
    cpos = cache["pos"].at[rows, slot].set(pos[:, 0])
    from repro.kernels import ops as kops
    if cfg.impl == "flash" and kops.flash_decode_gate(q.shape, k.shape, window):
        # Ragged per-slot-length kernel: a full-length unwindowed cache has
        # contiguous valid slots [0, idx], so the per-row length vector is
        # idx + 1 and the kernel's k loop stops at ceil(len/BLK) — HBM reads
        # scale with the row's actual length, not the cache capacity L.
        lengths = jnp.minimum(idx + 1, L)
        out = kops.flash_decode(q, k, v, lengths, scale=cfg.scale)
    else:
        out = _naive_attention(q, k, v, pos, cpos, causal=True, window=window,
                               scale=cfg.scale)
    y = out_proj(p["wo"], out)
    return y, {"k": k, "v": v, "pos": cpos}


# ================================================================= MLA ======
def mla_init(key: jax.Array, cfg: MLAConfig):
    ks = jax.random.split(key, 8)
    dm, H = cfg.d_model, cfg.num_heads
    qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {}
    if cfg.q_lora_rank:
        p["wdq"] = dense_init(ks[0], dm, cfg.q_lora_rank, ("embed", "qlora"))
        p["qnorm"] = rmsnorm_init(ks[1], cfg.q_lora_rank)
        p["wuq"] = _proj_init(ks[2], cfg.q_lora_rank, H, qk_dim, "heads")
    else:
        p["wq"] = _proj_init(ks[0], dm, H, qk_dim, "heads")
    p["wdkv"] = dense_init(ks[3], dm, cfg.kv_lora_rank, ("embed", "kvlora"))
    p["kvnorm"] = rmsnorm_init(ks[4], cfg.kv_lora_rank)
    p["wkr"] = dense_init(ks[4], dm, cfg.qk_rope_dim, ("embed", None))
    p["wuk"] = _proj_init(ks[5], cfg.kv_lora_rank, H, cfg.qk_nope_dim, "heads")
    p["wuv"] = _proj_init(ks[6], cfg.kv_lora_rank, H, cfg.v_head_dim, "heads")
    p["wo"] = _out_init(ks[7], H, cfg.v_head_dim, dm)
    return p


def _mla_q(p, x, q_pos, cfg: MLAConfig):
    if cfg.q_lora_rank:
        cq = rmsnorm(p["qnorm"], dense(p["wdq"], x))
        q = proj(p["wuq"], cq)
    else:
        q = proj(p["wq"], x)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, pos, cfg: MLAConfig):
    ckv = rmsnorm(p["kvnorm"], dense(p["wdkv"], x))          # (B, S, rank)
    kr = dense(p["wkr"], x)[:, :, None, :]                    # (B, S, 1, rope)
    kr = apply_rope(kr, pos, cfg.rope_theta)[:, :, 0, :]      # (B, S, rope)
    return ckv, kr


def mla_fwd(p, x, q_pos, cfg: MLAConfig, window=None, return_cache=False,
            segments=None):
    """Training / prefill MLA: expand compressed kv into per-head k/v.

    Dispatches the Pallas kernel with the SPLIT head dims — q/k carry
    qk_nope+qk_rope, v carries v_head_dim — via the kernels' independent
    Dv tiling (no concat/pad of v up to the qk dim)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x, q_pos, cfg)
    ckv, kr = _mla_ckv(p, x, q_pos, cfg)
    k_nope = proj(p["wuk"], ckv)
    v = proj(p["wuv"], ckv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(kr[:, :, None, :], (B, S, H, cfg.qk_rope_dim))],
                        axis=-1)
    out = attention(q, k, v, q_pos, q_pos, causal=True, window=window,
                    scale=cfg.scale, impl=cfg.impl, q_chunk=cfg.q_chunk,
                    k_chunk=cfg.k_chunk, segments=segments)
    y = out_proj(p["wo"], out)
    if return_cache:
        return y, {"ckv": ckv, "kr": kr, "pos": q_pos}
    return y


def mla_init_cache(cfg: MLAConfig, batch: int, length: int, dtype=jnp.bfloat16):
    return {"ckv": jnp.zeros((batch, length, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, length, cfg.qk_rope_dim), dtype),
            "pos": jnp.full((batch, length), -1, jnp.int32)}


def mla_decode(p, x, cache, index, cfg: MLAConfig):
    """Absorbed-matmul MLA decode against the compressed (c_kv, k_rope) cache.

    W_uk is folded into the query (q_abs = q_nope @ W_uk per head) so scores
    are taken directly against c_kv; W_uv is applied after the weighted sum,
    so neither K nor V is ever materialized per head.
    """
    B = x.shape[0]
    H, R = cfg.num_heads, cfg.kv_lora_rank
    L = cache["ckv"].shape[1]
    idx = decode_index(index, B)
    pos = idx[:, None]
    q_nope, q_rope = _mla_q(p, x, pos, cfg)                   # (B,1,H,nope/rope)
    ckv_new, kr_new = _mla_ckv(p, x, pos, cfg)
    slot = idx % L
    rows = jnp.arange(B)
    ckv = cache["ckv"].at[rows, slot].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    kr = cache["kr"].at[rows, slot].set(kr_new[:, 0].astype(cache["kr"].dtype))
    cpos = cache["pos"].at[rows, slot].set(pos[:, 0])

    wuk = p["wuk"]["kernel"]                                  # (R, H, nope)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(jnp.float32),
                       wuk.astype(jnp.float32))               # (B,1,H,R)
    s = (jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv.astype(jnp.float32))
         + jnp.einsum("bqhe,bse->bhqs", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * cfg.scale    # (B,H,1,S)
    bias = _mask_bias(pos, cpos, True, None)                  # (B,1,S)
    s = s + bias[:, None, :, :]
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w, ckv.astype(jnp.float32))  # (B,1,H,R)
    wuv = p["wuv"]["kernel"]                                  # (R, H, v)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, wuv.astype(jnp.float32))
    y = out_proj(p["wo"], out.astype(x.dtype))
    return y, {"ckv": ckv, "kr": kr, "pos": cpos}


# ======================================================== cross-attention ===
def cross_init(key: jax.Array, cfg: AttnConfig):
    ks = jax.random.split(key, 4)
    H, K, D, dm = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "wq": _proj_init(ks[0], dm, H, D, "heads"),
        "wk": _proj_init(ks[1], dm, K, D, "kv"),
        "wv": _proj_init(ks[2], dm, K, D, "kv"),
        "wo": _out_init(ks[3], H, D, dm),
    }


def cross_make_cache(p, enc_out, cfg: AttnConfig):
    """Project encoder output to K/V once (at prefill)."""
    B, Se, _ = enc_out.shape
    k = proj(p["wk"], enc_out)
    v = proj(p["wv"], enc_out)
    pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32)[None], (B, Se))
    return {"k": k, "v": v, "pos": pos}


def cross_fwd(p, x, cache, cfg: AttnConfig):
    """Decoder->encoder attention (no rope, bidirectional over encoder)."""
    B, S, _ = x.shape
    q = proj(p["wq"], x)
    q_pos = jnp.zeros((B, S), jnp.int32)
    out = attention(q, cache["k"], cache["v"], q_pos, cache["pos"],
                    causal=False, window=None, scale=cfg.scale,
                    impl=cfg.impl, q_chunk=cfg.q_chunk, k_chunk=cfg.k_chunk)
    return out_proj(p["wo"], out)
