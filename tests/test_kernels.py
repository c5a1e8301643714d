"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py).
Kernels execute in interpret mode on CPU; BlockSpec tiling targets TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("shape", [(8,), (100,), (256, 512), (1000, 37),
                                   (3, 17, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ladder", ["tpu", "gpu"])
@pytest.mark.parametrize("code", [0, 1, 2])
def test_qdq_cast(shape, dtype, ladder, code):
    x = (jax.random.normal(KEY, shape) * 3).astype(dtype)
    got = ops.qdq_cast(x, jnp.asarray(code), ladder)
    want = ref.qdq_cast_ref(x, jnp.asarray(code), ladder)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=0, atol=0)


@pytest.mark.parametrize("shape", [(64,), (513, 129), (1024, 512), (7, 3, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_stats(shape, dtype):
    x = (jax.random.normal(KEY, shape) * 2).astype(dtype)
    s, ss, mx = ops.grad_stats(x)
    rs, rss, rmx = ref.grad_stats_ref(x)
    np.testing.assert_allclose(float(s), float(rs), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(float(ss), float(rss), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(float(mx), float(rmx), rtol=0, atol=0)


@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("HK", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, HK, causal, window, dtype):
    H, K = HK
    B, D = 2, 64
    q = jax.random.normal(KEY, (B, S, H, D)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, D)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, D)).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_flash_matches_model_attention_path():
    """kernels.ops.flash_attention == nn.attention chunked path."""
    from repro.nn.attention import _chunked_attention
    B, S, H, K, D = 1, 512, 4, 2, 64
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, K, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, K, D))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    a = ops.flash_attention(q, k, v, causal=True, window=0)
    b = _chunked_attention(q, k, v, pos, pos, True, None, D ** -0.5, 256, 256)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


# ------------------------------------------- dispatch regressions (ISSUE 3) -
def _qkv(B=1, S=256, H=2, K=2, D=16):
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, D))
    return q, k, v


@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, 16)])
def test_flash_dispatch_honors_packed_positions(causal, window):
    """Non-arange positions (packed sequences: positions restart mid-row) on
    a kernel-eligible shape MUST match the naive oracle — the old dispatch
    sent them to the kernel, which rebuilt the mask from iota and silently
    masked the wrong pairs."""
    from repro.nn.attention import _naive_attention
    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    pos = jnp.broadcast_to((jnp.arange(S, dtype=jnp.int32) % 128)[None],
                           (B, S))
    got = ops.flash_attention(q, k, v, pos, pos, causal=causal, window=window)
    want = _naive_attention(q, k, v, pos, pos, causal, window, D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)
    # the mask genuinely differs from the arange one (the regression is real)
    arange_path = ops.flash_attention(q, k, v, causal=causal, window=window)
    assert not np.allclose(np.asarray(arange_path), np.asarray(want),
                           atol=1e-3)


def test_flash_dispatch_honors_masked_cache_slots():
    """k_pos rows containing -1 (empty cache slots) must stay masked."""
    from repro.nn.attention import _naive_attention
    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    qp = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    kp = qp.at[:, -64:].set(-1)
    got = ops.flash_attention(q, k, v, qp, kp, causal=True, window=None)
    want = _naive_attention(q, k, v, qp, kp, True, None, D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


def test_flash_dispatch_uses_kernel_for_concrete_arange():
    """CONCRETE standard-arange positions still take the kernel path — the
    guard only rejects positions it cannot prove standard."""
    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32)[None], (B, S))
    got = ops.flash_attention(q, k, v, jnp.asarray(pos), jnp.asarray(pos),
                              causal=True, window=16)
    want = ops.flash_attention(q, k, v, causal=True, window=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("np_window", [np.int64(32), np.int32(32)])
def test_flash_window_accepts_numpy_ints(np_window):
    """A numpy-integer window must window the kernel path — the old
    ``isinstance(window, int)`` coercion silently turned it into 0 (global
    attention) while the fallback paths windowed correctly."""
    q, k, v = _qkv()
    got = ops.flash_attention(q, k, v, causal=True, window=np_window)
    want = ops.flash_attention(q, k, v, causal=True, window=int(np_window))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    unwindowed = ops.flash_attention(q, k, v, causal=True, window=None)
    assert not np.allclose(np.asarray(got), np.asarray(unwindowed),
                           atol=1e-3)


def test_flash_kernel_reachable_under_jit_via_std_positions():
    """Under jit even arange-built positions are tracers, so the dispatch
    guard alone would send EVERY jitted model to the fallback. The
    ``std_positions`` hint (set by the code that constructs the positions —
    models/lm.py, models/encdec.py) must restore the kernel path, and a
    jitted call WITHOUT the hint must still fall back."""
    from repro.kernels import flash_attention as _fa
    from repro.nn.attention import attention, std_positions

    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    calls = []
    orig = _fa.flash_attention
    _fa.flash_attention = lambda *a, **kw: calls.append(1) or orig(*a, **kw)
    try:
        @jax.jit
        def f(q, k, v):
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
            with std_positions():
                return attention(q, k, v, pos, pos, causal=True, window=None,
                                 scale=D ** -0.5, impl="flash")
        out = f(q, k, v)
        assert calls, "kernel not dispatched under jit despite std hint"

        calls.clear()

        @jax.jit
        def g(q, k, v, pos):           # positions from outside: no hint
            return attention(q, k, v, pos, pos, causal=True, window=None,
                             scale=D ** -0.5, impl="flash")
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        out2 = g(q, k, v, pos)
    finally:
        _fa.flash_attention = orig
    assert not calls, "unproven positions must not reach the kernel"
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=3e-6)


# --------------------------------------- backward kernels / custom_vjp -----
def _grad_pair(fn_got, fn_want, q, k, v, atol):
    loss_g = lambda q, k, v: jnp.sum(jnp.square(
        fn_got(q, k, v).astype(jnp.float32)))
    loss_w = lambda q, k, v: jnp.sum(jnp.square(
        fn_want(q, k, v).astype(jnp.float32)))
    got = jax.grad(loss_g, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_w, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=atol,
                                   err_msg=name)


@pytest.mark.parametrize("HK", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad(HK, causal, window, dtype):
    """jax.grad through the kernel path (Pallas bwd kernels via custom_vjp)
    matches the jnp reference gradients across causal x window x GQA x
    dtype."""
    H, K = HK
    B, S, D = 2, 256, 32
    q = jax.random.normal(KEY, (B, S, H, D)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, D)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, D)).astype(dtype)
    atol = 5e-5 if dtype == jnp.float32 else 1.2e-1
    _grad_pair(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                            window=window),
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal,
                                                window=window),
        q, k, v, atol)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 300)])
def test_flash_attention_grad_multiblock(causal, window):
    """S > BQ: the dQ k-block sweep, the dK/dV q-block x head-group
    accumulation, and backward block skipping all cross tile boundaries
    (S=512 -> nq=nk=2), which the S=256 grid above never exercises."""
    B, S, H, K, D = 1, 512, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, D))
    _grad_pair(
        lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                            window=window),
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal,
                                                window=window),
        q, k, v, 1e-4)


def test_flash_grad_fallback_packed_positions():
    """Packed positions stay on the jnp fallback AND are differentiable —
    gradients match the naive oracle with the same positions."""
    from repro.nn.attention import _naive_attention
    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    pos = jnp.broadcast_to((jnp.arange(S, dtype=jnp.int32) % 128)[None],
                           (B, S))
    _grad_pair(
        lambda q, k, v: ops.flash_attention(q, k, v, pos, pos, causal=True,
                                            window=16),
        lambda q, k, v: _naive_attention(q, k, v, pos, pos, True, 16,
                                         D ** -0.5),
        q, k, v, 5e-5)


def test_flash_bwd_kernels_reached_under_jit():
    """Under jit + grad with the std-positions hint, the Pallas forward
    (residual-emitting) and backward kernels are the ones executing."""
    from conftest import count_flash_kernel_calls
    from repro.nn.attention import attention, std_positions

    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    with count_flash_kernel_calls() as calls:
        @jax.jit
        def g(q, k, v):
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
            with std_positions():
                out = attention(q, k, v, pos, pos, causal=True, window=None,
                                scale=D ** -0.5, impl="flash")
            return jnp.sum(jnp.square(out))

        jax.grad(g)(q, k, v)
    assert calls["fwd"] >= 1 and calls["bwd"] >= 1, calls


def test_flash_fallback_context_supports_jvp():
    """flash_fallback() pins dispatch to the jnp paths, which DO support
    forward-mode AD (the §3.2 curvature hvp = jvp of grad); without it the
    kernel path's custom_vjp rejects jvp."""
    B, S, D = 1, 256, 16
    q, k, v = _qkv(B=B, S=S, D=D)

    def loss(q):
        with ops.flash_fallback():
            return jnp.sum(jnp.square(ops.flash_attention(q, k, v)))

    g = lambda q: jax.grad(loss)(q)
    _, hv = jax.jvp(g, (q,), (jnp.ones_like(q),))
    assert np.isfinite(np.asarray(hv)).all()
    # without the context the kernel path rejects forward-mode (TypeError
    # from custom_vjp, or the Pallas jvp rule giving up first)
    with pytest.raises((TypeError, AssertionError, NotImplementedError)):
        bad = lambda q: jax.grad(
            lambda q: jnp.sum(jnp.square(ops.flash_attention(q, k, v))))(q)
        jax.jvp(bad, (q,), (jnp.ones_like(q),))


# ----------------------------------------------- fused qdq amax / padding --
def test_qdq_amax_argument_matches_fused():
    """Callers holding the grad_stats absmax skip the in-kernel reduction
    phase and get bit-identical output."""
    x = jax.random.normal(KEY, (300, 300)) * 2
    _, _, amax = ops.grad_stats(x)
    got = ops.qdq_cast(x, jnp.asarray(0), "tpu", amax=amax)
    want = ops.qdq_cast(x, jnp.asarray(0), "tpu")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("op", ["qdq", "stats"])
def test_block_aligned_fold_skips_pad_copy(op):
    """Block-aligned tensors (the weight-matrix common case) must reshape in
    place — no zeros+scatter pad; ragged tails still pad."""
    if op == "qdq":
        fn = lambda x: ops.qdq_cast(x, jnp.asarray(1), "tpu")
    else:
        fn = lambda x: ops.grad_stats(x)
    aligned = str(jax.make_jaxpr(fn)(jnp.ones((1024, 512))))
    ragged = str(jax.make_jaxpr(fn)(jnp.ones((1000, 37))))
    assert "scatter" not in aligned
    assert "scatter" in ragged


@pytest.mark.parametrize("shape", [(8,), (64,), (300,), (1000,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_stats_small_leaf_no_block_pad(shape, dtype):
    """Sub-block leaves (biases, norm scales) take the small-tile path —
    no 256x512 = 128K-element zero-pad (the old min_rows=BLOCK_M cost) —
    and still match the oracle."""
    x = (jax.random.normal(KEY, shape) * 2).astype(dtype)
    s, ss, mx = ops.grad_stats(x)
    rs, rss, rmx = ref.grad_stats_ref(x)
    np.testing.assert_allclose(float(s), float(rs), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(float(ss), float(rss), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(float(mx), float(rmx), rtol=0, atol=0)
    from repro.analysis import pallas_calls
    calls = pallas_calls(jax.make_jaxpr(lambda x: ops.grad_stats(x))(x))
    assert calls, "grad_stats no longer lowers through pallas_call"
    for call in calls:
        for blk in call.blocks:
            assert blk.block_elems < 256 * 512, (
                f"small leaf padded to a full 256x512 block: "
                f"{blk.block_shape} in {call.locus}")


def test_small_blocks_selection():
    from repro.kernels.layout import small_blocks
    assert small_blocks(256 * 512) == (256, 512)      # full tile stays
    assert small_blocks(10_000_000) == (256, 512)
    bm, bn = small_blocks(64)                          # one tiny tile
    assert bn == 128 and bm == 16
    bm, bn = small_blocks(8 * 512)                     # mid: full-width rows
    assert bn == 512 and bm == 16


# ------------------------------------------------------- bench smoke (CI) --
@pytest.mark.slow
def test_kernels_bench_emits_all_rows(capsys):
    """benchmarks/kernels_bench.py as a CI smoke leg: every CSV row —
    including the new fwd+bwd timings over the seqlen sweep — must be
    emitted (interpret mode)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import kernels_bench
    kernels_bench.main()
    out = capsys.readouterr().out
    expected = ["qdq_cast_pallas_1M", "qdq_cast_ref_1M",
                "grad_stats_pallas_1M", "grad_stats_ref_1M"]
    for S in kernels_bench.ATTN_SEQ_SWEEP:
        for impl in ("flash", "chunked"):
            expected += [f"attn_{impl}_fwd_S{S}", f"attn_{impl}_fwdbwd_S{S}"]
    for n in kernels_bench.UPDATE_PARAM_SWEEP:
        expected += [f"update_resident_{n}", f"update_resident_sr_{n}",
                     f"update_packed_{n}", f"update_ref_{n}"]
    for name in expected:
        assert f"kernels:{name}," in out, name
    # the bytes model the sweep prints: fused <= 2 gradient-footprint
    # reads + 2 writes vs >= 6 reads on the reference path
    from repro.roofline.costmodel import update_phase_bytes
    for n in kernels_bench.UPDATE_PARAM_SWEEP:
        grad_bytes = 4.0 * n
        fused = update_phase_bytes(n, slots=1, fused=True)
        ref_b = update_phase_bytes(n, slots=1, fused=False)
        # fused: 2 grad reads + master/slot state + 2 writes incl. the copy
        assert fused <= (2 + 2) * grad_bytes + 2 * (1 + 1) * grad_bytes
        assert ref_b >= 6 * grad_bytes          # >= 6 gradient reads today
        assert fused < 0.5 * ref_b


def test_flash_window_numpy_int_on_fallback_path():
    """Same numpy-int window on a non-kernel shape (S not divisible by the
    block size) — both paths must agree with the windowed naive oracle."""
    from repro.nn.attention import _naive_attention
    B, S, D = 1, 64, 16
    q, k, v = _qkv(B=B, S=S, D=D)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    got = ops.flash_attention(q, k, v, causal=True, window=np.int64(8))
    want = _naive_attention(q, k, v, pos, pos, True, 8, D ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


# ------------------------------------------------------ flash tile tables --
@pytest.mark.parametrize("rep", [0, 3], ids=["fwd_dq", "dkv"])
@pytest.mark.parametrize("window", [0, 100, 300, 700])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("nk", [1, 2, 4, 8])
@pytest.mark.parametrize("nq", [1, 2, 4, 8])
def test_flash_tile_table(nq, nk, causal, window, rep):
    """The table lists exactly the tiles ``_block_needed`` keeps, in the
    dense walk's order; each output block (the q-block, or the k-block of
    the dK/dV walk) appears, as one run flagged first at its start and last
    at its end. A block with no needed tile — possible only where the q and
    k lengths differ — is refused rather than left unwritten."""
    from repro.kernels import flash_attention as fa

    def needed(qi, ki):
        return fa._block_needed(qi * fa.BQ, ki * fa.BK, causal, window)

    if rep:
        want = [(qi, ki, r) for ki in range(nk) for r in range(rep)
                for qi in range(nq) if needed(qi, ki)]
        n_out, out_of = nk, lambda e: e[1]
    else:
        want = [(qi, ki, 0) for qi in range(nq) for ki in range(nk)
                if needed(qi, ki)]
        n_out, out_of = nq, lambda e: e[0]
    if {out_of(e) for e in want} != set(range(n_out)):
        assert nq != nk
        with pytest.raises(ValueError):
            fa._tile_table(nq, nk, causal, window, rep=rep)
        return
    tbl = fa._tile_table(nq, nk, causal, window, rep=rep)
    assert tbl.dtype == np.int32
    got = list(zip(np.asarray(fa._qi(tbl)).tolist(),
                   np.asarray(fa._ki(tbl)).tolist(),
                   np.asarray(fa._r(tbl)).tolist()))
    assert got == want
    first, last = np.asarray(fa._first(tbl)), np.asarray(fa._last(tbl))
    for b in range(n_out):
        idx = [i for i, e in enumerate(got) if out_of(e) == b]
        assert idx == list(range(idx[0], idx[-1] + 1))   # one run
        assert np.flatnonzero(first[idx]).tolist() == [0]
        assert np.flatnonzero(last[idx]).tolist() == [len(idx) - 1]
    if nq == nk and causal and not window:
        assert tbl.size == max(rep, 1) * nq * (nq + 1) // 2


def _doc_ids(B, S, bounds):
    """Non-decreasing doc ids that change at each of ``bounds``."""
    ids = jnp.searchsorted(jnp.asarray(bounds), jnp.arange(S), side="right")
    return jnp.broadcast_to(ids.astype(jnp.int32)[None], (B, S))


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 300),
                                           (False, 0), (False, 700)])
def test_flash_attention_nq4(causal, window, packed):
    """S=1024 (nq = nk = 4) with GQA (4, 2): the causal table skips 6 of
    16 tiles, a window drops whole tiles beside the diagonal too, and packed
    rows (documents ending off the tile edges) add the runtime segment skip
    on top. Forward and all three gradients against the reference."""
    B, S, H, K, D = 1, 1024, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, D))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, D))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, D))
    seg = _doc_ids(B, S, [300, 512, 700]) if packed else None

    def got(q, k, v):
        return ops.flash_attention(q, k, v, segments=seg, causal=causal,
                                   window=window)

    def want(q, k, v):
        return ref.flash_attention_ref(q, k, v, segments=seg, causal=causal,
                                       window=window)

    np.testing.assert_allclose(np.asarray(got(q, k, v)),
                               np.asarray(want(q, k, v)), atol=5e-6)
    _grad_pair(got, want, q, k, v, 1e-4)


def _grid_records():
    from repro import obs
    return {r.attrs["kernel"]: (r.attrs["launched"], r.attrs["dense"])
            for r in obs.spans() if r.name == "flash.grid"}


def test_flash_grid_recorded_by_traced_calls():
    """Tracing the kernels records each call's grid steps: a tiny causal
    trainer run launches fewer than the dense walk in all three kernels
    (3 of 4 tiles at nq = nk = 2); a non-causal call launches all."""
    from repro import obs
    from repro.core.precision import TriAccelConfig
    from repro.models.lm import LMConfig
    from repro.nn.attention import AttnConfig
    from repro.nn.blocks import BlockDef, StackConfig
    from repro.train.trainer import Trainer, TrainerConfig

    # shapes no other test traces, so the kernels' jit caches miss
    attn = AttnConfig(d_model=24, num_heads=3, num_kv_heads=1, head_dim=8,
                      impl="flash")
    sc = StackConfig(segments=(((BlockDef("gqa", "dense"),), 1),),
                     d_model=24, d_ff=48, attn=attn, remat=False)
    cfg = LMConfig(name="tiny-flash", family="dense", vocab_size=64,
                   stack=sc, compute_dtype=jnp.float32)
    tac = TriAccelConfig(ladder="tpu", t_ctrl=2, enable_curvature=False)
    obs.clear()
    tr = Trainer(cfg, tac, TrainerConfig(total_steps=2, seq_len=512,
                                         rungs=(2,)))
    tr.warm_rungs()
    tr.run(1)
    grids = _grid_records()
    assert set(grids) == {"fwd", "dq", "dkv"}, grids
    B, H, K = 2, 3, 1
    assert grids["fwd"] == grids["dq"] == (B * H * 3, B * H * 4)
    assert grids["dkv"] == (B * K * 3 * 3, B * K * 3 * 4)

    obs.clear()
    q = jax.random.normal(KEY, (1, 512, 3, 8))
    kv = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 512, 1, 8))
    jax.jit(jax.grad(lambda q: jnp.sum(ops.flash_attention(
        q, kv, kv, causal=False))))(q)
    grids = _grid_records()
    assert set(grids) == {"fwd", "dq", "dkv"}, grids
    assert all(launched == dense for launched, dense in grids.values())
