"""Unit + property tests for the paper's core: §3.1 precision law,
§3.2 curvature, §3.3 batch controller, §3.4 control loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:           # degrade gracefully: run fixed examples
    given = settings = st = None

from repro.core import curvature as curv
from repro.core.batch_scaler import BatchScaler, MemoryModel
from repro.core.controller import (init_control, lr_scales, update_control,
                                   with_curvature)
from repro.core.grouping import flat_grouping
from repro.core.precision import (TriAccelConfig, codes_from_stats, qdq,
                                  variance_from_moments)


# ------------------------------------------------------------- §3.1 -------
def test_threshold_law_matches_paper():
    tac = TriAccelConfig(tau_low=1e-6, tau_high=1e-3, enable_curvature=False)
    v = jnp.array([1e-8, 1e-6, 5e-4, 1e-3, 1.0])
    codes = codes_from_stats(v, jnp.zeros_like(v), tac)
    assert list(np.asarray(codes)) == [0, 1, 1, 2, 2]


def test_curvature_promotion_overrides():
    tac = TriAccelConfig(tau_low=1e-6, tau_high=1e-3, tau_curv=5.0)
    v = jnp.array([1e-8, 1e-8])
    lam = jnp.array([0.0, 10.0])
    codes = codes_from_stats(v, lam, tac)
    assert list(np.asarray(codes)) == [0, 2]


def _check_codes_monotone(vs):
    """Higher variance never gets LOWER precision (monotone law)."""
    tac = TriAccelConfig(enable_curvature=False)
    v = jnp.asarray(sorted(vs), jnp.float32)
    codes = np.asarray(codes_from_stats(v, jnp.zeros_like(v), tac))
    assert (np.diff(codes) >= 0).all()


def _check_qdq_idempotent(code):
    x = jax.random.normal(jax.random.PRNGKey(0), (64,)) * 2
    once = qdq(x, jnp.asarray(code), "gpu")
    twice = qdq(once, jnp.asarray(code), "gpu")
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


if st is not None:
    @given(st.lists(st.floats(1e-10, 1e2), min_size=1, max_size=32))
    @settings(max_examples=30, deadline=None)
    def test_codes_monotone_in_variance(vs):
        _check_codes_monotone(vs)

    @given(st.integers(0, 2))
    @settings(max_examples=9, deadline=None)
    def test_qdq_idempotent(code):
        _check_qdq_idempotent(code)
else:
    def test_codes_monotone_in_variance():
        _check_codes_monotone([1e-10, 1e-7, 5e-4, 1e-3, 1e2])

    @pytest.mark.parametrize("code", [0, 1, 2])
    def test_qdq_idempotent(code):
        _check_qdq_idempotent(code)


def test_variance_from_moments():
    x = jax.random.normal(jax.random.PRNGKey(1), (1000,))
    s, ss, cnt = jnp.sum(x), jnp.sum(x * x), jnp.asarray(1000.0)
    np.testing.assert_allclose(float(variance_from_moments(s, ss, cnt)),
                               float(jnp.var(x)), rtol=1e-5)


# ------------------------------------------------------------- §3.2 -------
def test_power_iteration_exact_on_quadratic():
    d = jnp.array([1.0, 4.0, 9.0])
    params = {"a": jnp.ones(3)}
    loss = lambda p: 0.5 * jnp.sum(d * p["a"] ** 2)
    lam = curv.power_iteration_layer(loss, params, lambda path: True,
                                     jax.random.PRNGKey(0), 30)
    np.testing.assert_allclose(float(lam), 9.0, rtol=1e-4)


def test_hutchinson_matches_trace_on_quadratic():
    d = jnp.array([2.0, 4.0, 6.0, 8.0])
    params = {"w": jnp.ones(4)}
    loss = lambda p: 0.5 * jnp.sum(d * p["w"] ** 2)
    grp = flat_grouping(params)
    tr = curv.hutchinson_layer_traces(loss, params, grp.mean,
                                      jax.random.PRNGKey(0), 64)
    np.testing.assert_allclose(float(tr[0]), 5.0, rtol=0.05)


def test_curvature_probes_distinct_across_same_shape_layers():
    """Regression: per-leaf Rademacher draws keyed ``hash(l.shape)`` gave
    every same-shape layer the IDENTICAL probe vector (fully correlated
    estimates). Probes must be independent per leaf."""
    params = {"a": jnp.ones((64,)), "b": jnp.ones((64,)), "c": jnp.ones((64,))}
    v = curv._rademacher_tree(params, jax.random.PRNGKey(0))
    for x, y in [("a", "b"), ("a", "c"), ("b", "c")]:
        assert not np.array_equal(np.asarray(v[x]), np.asarray(v[y])), (x, y)


def test_power_iteration_per_layer_blocks_same_shape():
    """Two same-shape blocks with different spectra: each per-layer power
    iteration must recover ITS block's top eigenvalue (with correlated
    probes both blocks started from the same vector)."""
    da, db = jnp.array([1.0, 4.0, 9.0]), jnp.array([25.0, 2.0, 3.0])
    params = {"a": jnp.ones(3), "b": jnp.ones(3)}
    loss = lambda p: 0.5 * (jnp.sum(da * p["a"] ** 2)
                            + jnp.sum(db * p["b"] ** 2))
    key = jax.random.PRNGKey(0)
    lam_a = curv.power_iteration_layer(
        loss, params, lambda path: path[0].key == "a", key, 30)
    lam_b = curv.power_iteration_layer(
        loss, params, lambda path: path[0].key == "b", key, 30)
    np.testing.assert_allclose(float(lam_a), 9.0, rtol=1e-4)
    np.testing.assert_allclose(float(lam_b), 25.0, rtol=1e-4)


def test_lr_scales_law():
    tac = TriAccelConfig(alpha=0.5)
    ctl = with_curvature(init_control(3, tac), jnp.array([0.0, 2.0, 10.0]))
    s = np.asarray(lr_scales(ctl, tac))
    np.testing.assert_allclose(s, [1.0, 1 / 2.0, 1 / 6.0], rtol=1e-6)


# ------------------------------------------------------------- §3.3 -------
def _scaler(cap_gb=16.0, rungs=(8, 16, 32, 64), act_per_tok=1e5,
            params=5e7):
    tac = TriAccelConfig(mem_cap_bytes=cap_gb * 1e9, rho_low=0.8, rho_high=0.92)
    mm = MemoryModel(param_count=params, opt_slots=1,
                     act_bytes_per_token_layer=act_per_tok, num_layers=10,
                     fixed_overhead=0)
    return BatchScaler(rungs, 128, mm, tac), tac


def test_scaler_climbs_when_underutilized():
    sc, _ = _scaler(cap_gb=1e3)
    r0 = sc.microbatch
    for i in range(10):
        sc.observe(i)
    assert sc.microbatch == sc.rungs[-1] >= r0


def test_scaler_never_exceeds_cap_estimate():
    sc, tac = _scaler(cap_gb=2.0)
    for i in range(20):
        sc.observe(i)
        assert sc.model.total(sc.microbatch * sc.seq_len) \
            <= tac.rho_high * tac.mem_cap_bytes * 1.001


def test_scaler_backs_off_on_measured_pressure():
    sc, tac = _scaler(cap_gb=1e3)
    for i in range(10):
        sc.observe(i)
    hi = sc.microbatch
    sc.observe(99, measured_bytes=0.95 * tac.mem_cap_bytes)
    assert sc.microbatch < hi


def _check_rung_always_valid(measured):
    sc, _ = _scaler()
    for i, m in enumerate(measured):
        r = sc.observe(i, measured_bytes=m)
        assert r in sc.rungs


if st is not None:
    @given(st.lists(st.floats(0, 2e10), min_size=1, max_size=50))
    @settings(max_examples=30, deadline=None)
    def test_scaler_rung_always_valid(measured):
        _check_rung_always_valid(measured)
else:
    def test_scaler_rung_always_valid():
        _check_rung_always_valid([0.0, 2e10, 1e9, 1.5e10, 5e8, 2e10, 0.0])


def test_precision_codes_shrink_modeled_memory():
    """closed loop: lower-precision codes -> smaller modeled activations ->
    room for a bigger batch (the paper's §3.4 interplay)."""
    mm = MemoryModel(param_count=1e6, opt_slots=1,
                     act_bytes_per_token_layer=1e5, num_layers=10,
                     fixed_overhead=0)
    hi = mm.total(1000, codes=[2] * 10, ladder="gpu")
    mid = mm.total(1000, codes=[1] * 10, ladder="gpu")
    lo = mm.total(1000, codes=[0] * 10, ladder="tpu")
    assert lo < mid < hi


# ------------------------------------------------------------- §3.4 -------
def test_control_loop_ema_and_refresh_cadence():
    tac = TriAccelConfig(beta=0.5, t_ctrl=2, tau_low=1e-9, tau_high=1e3,
                         ladder="tpu")
    ctl = init_control(2, tac)
    mom = (jnp.array([0.0, 0.0]), jnp.array([4.0, 16.0]), jnp.array([4.0, 4.0]))
    ctl1 = update_control(ctl, mom, tac, jnp.asarray(True))
    # first step seeds the EMA directly
    np.testing.assert_allclose(np.asarray(ctl1.var_ema), [1.0, 4.0])
    ctl2 = update_control(ctl1, mom, tac, jnp.asarray(True))
    np.testing.assert_allclose(np.asarray(ctl2.var_ema), [1.0, 4.0])
    # codes refresh only on the t_ctrl cadence
    assert int(ctl1.step) == 1 and int(ctl2.step) == 2


def test_loss_scale_halves_on_overflow():
    tac = TriAccelConfig(ladder="gpu")
    ctl = init_control(1, tac)
    mom = (jnp.zeros(1), jnp.ones(1), jnp.ones(1))
    bad = update_control(ctl, mom, tac, jnp.asarray(False))
    assert float(bad.loss_scale) == float(ctl.loss_scale) / 2
    good = update_control(ctl, mom, tac, jnp.asarray(True))
    assert float(good.loss_scale) == float(ctl.loss_scale)


# ---------------------------------------------------- device facts --------
class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "x", stats

    def memory_stats(self):
        return self._stats


def test_mem_cap_comes_from_the_device():
    from repro.core.batch_scaler import (CPU_MEM_CAP, device_mem_cap,
                                         with_device_cap)
    tpu = _FakeDevice("tpu", {"bytes_limit": 15.75e9, "bytes_in_use": 1})
    assert device_mem_cap(tpu) == 15.75e9
    assert with_device_cap(TriAccelConfig(), tpu).mem_cap_bytes == 15.75e9
    # an explicit cap wins; the CPU backend reports no limit
    assert with_device_cap(TriAccelConfig(mem_cap_bytes=4e9),
                           tpu).mem_cap_bytes == 4e9
    assert device_mem_cap(_FakeDevice("cpu", None)) == CPU_MEM_CAP
    # an accelerator that reports nothing is an error, not 16 GB
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_mem_cap(_FakeDevice("tpu", {}))


def test_gpu_ladder_refused_for_tpu_kernels():
    from repro.core.precision import check_ladder_kernels
    with pytest.raises(ValueError, match="fp16"):
        check_ladder_kernels("gpu", "tpu")
    check_ladder_kernels("tpu", "tpu")
    check_ladder_kernels("gpu", "cpu")


def test_fp8_qdq_is_straight_through():
    """The low-tier QDQ's gradient is the identity: differentiating the
    e4m3 casts would round the cotangent onto the unscaled fp8 grid (NaN
    above 448) and skip every low-tier step on the reference path."""
    from repro.core.precision import qdq
    x = jnp.linspace(-3.0, 3.0, 64)
    g = jax.grad(lambda x: jnp.sum(qdq(x, 0, "tpu") * 1000.0))(x)
    np.testing.assert_array_equal(np.asarray(g), np.full(64, 1000.0))
