"""jit'd public wrappers for the Pallas kernels.

Off-TPU (this CPU container, unit tests) the kernels execute in interpret
mode — the same kernel body traced with jnp semantics — so correctness is
validated everywhere while the BlockSpec tiling targets TPU.

``flash_attention`` here is a DIFFERENTIABLE op: the kernel path is bound
to the Pallas backward kernels with ``jax.custom_vjp`` (forward emits the
logsumexp residual; backward reduces the dO·O row sums in XLA, then runs
the dQ and dK/dV kernels), and the dispatch gate guards the whole
differentiable op — a configuration the kernel cannot handle falls back to
the chunked/naive jnp paths, which JAX differentiates natively. On a
multi-device activation mesh the kernels run per shard under
``shard_map`` (GSPMD does not partition Mosaic kernels). One asymmetry of
custom_vjp: forward-mode AD (jax.jvp, used by the §3.2 curvature HVPs)
cannot pass through it — trace-time callers that need jvp wrap themselves
in ``flash_fallback()`` (repro.train.task.curvature_loss does), which pins
dispatch to the jnp paths.
"""
from __future__ import annotations

import contextlib
import functools
import operator
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_update as _fu
from repro.kernels import grad_stats as _gs
from repro.kernels import qdq_cast as _qc


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def qdq_cast(x, code, ladder: str = "tpu", amax=None):
    return _qc.qdq_cast(x, code, ladder=ladder, interpret=_interpret(),
                        amax=amax)


def grad_stats(x):
    return _gs.grad_stats(x, interpret=_interpret())


def fused_stats(g_slab, row_layer, num_layers: int):
    """Phase 1 of the fused update: one gradient read -> per-layer
    (sum, sum_sq, absmax, nonfinite_count)."""
    return _fu.fused_stats(g_slab, row_layer, num_layers,
                           interpret=_interpret())


def fused_apply(g_slab, p_slab, m_slab, v_slab, scalars, row_layer,
                lr_rows, code_rows, qs_rows, *, spec, ladder, cp_dtype,
                num_layers, sr: bool = False):
    """Phase 2 of the fused update: final gradient read -> optimizer step,
    fp32 master write, next-step compute copy (``sr=True`` casts it with
    stochastic rounding, seeded from ``scalars[4]``), per-layer param
    absmax."""
    return _fu.fused_apply(g_slab, p_slab, m_slab, v_slab, scalars,
                           row_layer, lr_rows, code_rows, qs_rows, spec=spec,
                           ladder=ladder, cp_dtype=cp_dtype,
                           num_layers=num_layers, interpret=_interpret(),
                           sr=sr)


# ------------------------------------------------------------ dispatch -----
_FALLBACK = threading.local()


@contextlib.contextmanager
def flash_fallback(flag: bool = True):
    """Trace-time escape hatch: force ``flash_attention`` below onto the jnp
    fallback paths even when the kernel gate holds. Needed wherever the op
    must support forward-mode AD (custom_vjp has no jvp rule) — the §3.2
    curvature probes differentiate the loss with jvp-of-grad."""
    prev = getattr(_FALLBACK, "flag", False)
    _FALLBACK.flag = bool(flag)
    try:
        yield
    finally:
        _FALLBACK.flag = prev


def _static_window(window):
    """Concrete integral window -> python int (0 = unwindowed); ``None`` for
    a traced value the kernel cannot specialize on. ``operator.index`` keeps
    numpy integers (np.int64 configs) intact — the old ``isinstance(window,
    int)`` check silently turned them into 0 = no window on the kernel path
    while the fallback paths windowed correctly."""
    if window is None:
        return 0
    try:
        return operator.index(window)
    except TypeError:
        return None


def _is_std_arange(pos, batch: int, seqlen: int) -> bool:
    """True when ``pos`` is STATICALLY known to be the standard arange the
    kernel's iota-based mask hard-codes: None, or a concrete (B, S) array
    equal to broadcast arange(S). A traced array can encode packed/offset
    sequences, so it is never provably standard -> False (fallback)."""
    if pos is None:
        return True
    if isinstance(pos, jax.core.Tracer):
        return False
    arr = np.asarray(pos)
    if arr.shape != (batch, seqlen):
        return False
    return bool((arr == np.arange(seqlen, dtype=arr.dtype)[None]).all())


def kernel_shape_gate(q_shape, k_shape, v_shape) -> bool:
    """Static part of the dispatch gate, shared with the roofline cost model
    (roofline.costmodel.flash_skip_flags): self-attention with Sq == Sk
    divisible by both block sizes and matching q/k head dims. The value head
    dim is tiled INDEPENDENTLY (its own Dv BlockSpecs/accumulators), so MLA
    training — qk dim (nope+rope) != v_head_dim — runs the real kernel."""
    Sq, Sk = q_shape[1], k_shape[1]
    return (Sq == Sk and Sq % _fa.BQ == 0 and Sq % _fa.BK == 0
            and q_shape[-1] == k_shape[-1])


def kernel_fallback_reason(q_shape, k_shape, v_shape, q_pos, k_pos,
                           window, segments=None) -> str:
    """Why the differentiable kernel op cannot take this call — "" when it
    can. Mirrors the dispatch in ``flash_attention`` below; the cost model
    surfaces the same taxonomy (flash_skip_flags' ``reason`` field) so
    dryrun cells say why a config priced the chunked path."""
    B, Sq = q_shape[0], q_shape[1]
    Sk = k_shape[1]
    if _static_window(window) is None:
        return "traced window (kernel specializes on a static window)"
    if Sq != Sk:
        return f"cross-length attention Sq={Sq} != Sk={Sk}"
    if Sq % _fa.BQ or Sq % _fa.BK:
        return (f"seq len {Sq} not divisible by kernel blocks "
                f"({_fa.BQ}/{_fa.BK})")
    if q_shape[-1] != k_shape[-1]:
        return f"q/k head dims differ ({q_shape[-1]} vs {k_shape[-1]})"
    if segments is not None:
        if q_pos is not None or k_pos is not None:
            return ("packed segments with undeclared positions (wrap the "
                    "constructor in nn.attention.segment_positions)")
        return ""
    if not (_is_std_arange(q_pos, B, Sq) and _is_std_arange(k_pos, B, Sk)):
        return ("positions not provably the standard arange (packed/offset "
                "batch without segment ids)")
    return ""


_WARNED_FALLBACKS = set()


def _note_fallback(reason: str) -> None:
    """Warn ONCE per fallback reason category: the jnp paths are correct
    but silently pay full-window FLOPs — a perf cliff worth surfacing."""
    if reason and reason not in _WARNED_FALLBACKS:
        _WARNED_FALLBACKS.add(reason)
        warnings.warn(
            f"flash_attention: kernel gate failed ({reason}); running the "
            "chunked/naive jnp fallback", stacklevel=3)


def _per_shard(fn, q, k, v, *segments):
    """Run a Pallas attention op on each device's shard of the activation
    mesh (``launch.sharding.activation_mesh``): GSPMD does not partition
    Mosaic kernels, so ``shard_map`` hands every device its batch rows (and
    its heads, when the "model" axis divides both head counts)."""
    from repro.launch.sharding import current_mesh, fsdp_axes
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(q, k, v, *segments)
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    dp = fsdp_axes(mesh)
    rows = (dp if len(dp) > 1 else dp[0]) if dp else None
    m = mesh.shape.get("model", 1)
    heads = ("model" if m > 1 and q.shape[2] % m == 0
             and k.shape[2] % m == 0 else None)
    spec = P(rows, None, heads, None)
    return shard_map(fn, mesh=mesh,
                     in_specs=(spec,) * 3 + (P(rows, None),) * len(segments),
                     out_specs=spec, check_rep=False)(q, k, v, *segments)


# ----------------------------------------------- differentiable kernel op --
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, window, scale, interpret):
    # primal (no differentiation): forward kernel without the residual write
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, window, scale, interpret):
    o, lse = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                     scale=scale, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_diff_bwd(causal, window, scale, interpret, res, do):
    q, k, v, o, lse = res
    return _fa.flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                                   window=window, scale=scale,
                                   interpret=interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


# Segment-masked variant: ``segments`` is a traced int32 operand on the
# differentiable path, so it rides as a primal arg whose cotangent is the
# mandatory float0 zero (int inputs carry no tangent space).
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_diff_seg(q, k, v, segments, causal, window, scale, interpret):
    return _fa.flash_attention(q, k, v, segments, causal=causal,
                               window=window, scale=scale,
                               interpret=interpret)


def _flash_diff_seg_fwd(q, k, v, segments, causal, window, scale, interpret):
    o, lse = _fa.flash_attention_fwd(q, k, v, segments, causal=causal,
                                     window=window, scale=scale,
                                     interpret=interpret)
    return o, (q, k, v, o, lse, segments)


def _flash_diff_seg_bwd(causal, window, scale, interpret, res, do):
    q, k, v, o, lse, segments = res
    dq, dk, dv = _fa.flash_attention_bwd(q, k, v, o, lse, do, segments,
                                         causal=causal, window=window,
                                         scale=scale, interpret=interpret)
    return dq, dk, dv, np.zeros(segments.shape, jax.dtypes.float0)


_flash_diff_seg.defvjp(_flash_diff_seg_fwd, _flash_diff_seg_bwd)


def flash_attention(q, k, v, q_pos=None, k_pos=None, *, segments=None,
                    causal=True, window=None, scale=None):
    """Drop-in for repro.nn.attention.attention that dispatches the Pallas
    kernel ONLY for configurations it computes correctly: self-attention
    (Sq == Sk) divisible by the block sizes, matching q/k head dims (Dv is
    free — MLA runs the kernel), a static integral window, and EITHER
    positions statically equal to the standard arange (train/prefill) OR
    ``segments`` with positions declared segment-standard (packed batches:
    q_pos/k_pos passed as None under nn.attention.segment_positions, the
    within-segment arange contract the segment kernels assume). Everything
    else — ragged/offset positions, traced windows, tiny sequences — runs
    the chunked or naive jnp path with positions AND segments honored. BOTH
    paths are differentiable: the kernel through its custom_vjp backward
    kernels, the fallbacks through JAX AD."""
    B, Sq = q.shape[0], q.shape[1]
    Sk = k.shape[1]
    win = _static_window(window)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    forced = getattr(_FALLBACK, "flag", False)
    reason = kernel_fallback_reason(q.shape, k.shape, v.shape, q_pos, k_pos,
                                    window, segments)
    if not forced and not reason:
        static = (bool(causal), win, float(scale), _interpret())
        if segments is not None:
            return _per_shard(
                lambda q, k, v, s: _flash_diff_seg(q, k, v, s, *static),
                q, k, v, segments)
        return _per_shard(lambda q, k, v: _flash_diff(q, k, v, *static),
                          q, k, v)
    if not forced:
        _note_fallback(reason)
    from repro.nn.attention import _chunked_attention, _naive_attention
    if win is not None:                 # normalized static window (int or off)
        window = win if win > 0 else None
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
    if k_pos is None:
        k_pos = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32)[None], (B, Sk))
    if Sq % _fa.BQ == 0 and Sk % _fa.BK == 0:
        return _chunked_attention(q, k, v, q_pos, k_pos, causal, window,
                                  scale, _fa.BQ, _fa.BK,
                                  q_seg=segments, k_seg=segments)
    return _naive_attention(q, k, v, q_pos, k_pos, causal, window, scale,
                            q_seg=segments, k_seg=segments)


# --------------------------------------------------------- ragged decode --
def flash_decode_gate(q_shape, k_shape, window) -> bool:
    """Static gate for the ragged decode kernel: single-token query, an
    unwindowed full-length cache (ring-wrapped windowed caches are not a
    contiguous [0, len) prefix), matching q/k head dims, and a cache length
    the decode blocks tile. ``flash_fallback()`` pins decode to the naive
    path too (trace-time flag, so the branch is resolved at trace time)."""
    return (window is None and q_shape[1] == 1
            and q_shape[-1] == k_shape[-1]
            and _fa.decode_block(k_shape[1]) is not None
            and not getattr(_FALLBACK, "flag", False))


def flash_decode(q, k, v, lengths, *, scale=None):
    """Ragged per-slot-length decode kernel (see kernels.flash_attention
    .flash_decode): row b of the (B, 1, H, D) query attends cache slots
    [0, lengths[b]) only. Callers gate with ``flash_decode_gate``."""
    return _fa.flash_decode(q, k, v, lengths, scale=scale,
                            interpret=_interpret())
