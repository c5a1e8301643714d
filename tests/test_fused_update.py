"""Fused update phase (DESIGN.md §9): parity of the Pallas slab sweep
against the jnp reference (opt.update + apply_updates), bit-exact master
trajectories, the cast_params elimination, zero-recompile across precision
codes, the accum trace-time guard, and the absmax-table reuse."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import init_control
from repro.core.grouping import flat_grouping
from repro.core.precision import TriAccelConfig
from repro.data.synthetic import LMTaskStream
from repro.models.lm import LMConfig
from repro.nn.attention import AttnConfig
from repro.nn.blocks import BlockDef, StackConfig
from repro.nn.module import split_params
from repro.optim.optimizers import adamw, sgdm
from repro.train.task import LMTask, TrainTask
from repro.train.train_step import (TrainState, init_compute,
                                    make_train_step, split_microbatches)

KEY = jax.random.PRNGKey(7)


def _tiny_lm(compute=jnp.float32):
    attn = AttnConfig(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                      impl="naive")
    sc = StackConfig(segments=(((BlockDef("gqa", "dense"),), 2),),
                     d_model=64, d_ff=128, attn=attn, remat=False)
    return LMConfig(name="tiny", family="dense", vocab_size=64, stack=sc,
                    compute_dtype=compute)


def _fixture(opt, compute=jnp.float32, ladder="tpu", codes=None, **tac_kw):
    task = LMTask(_tiny_lm(compute))
    params, _ = split_params(task.init(jax.random.PRNGKey(0))[0])
    grouping = task.grouping(params)
    tac = TriAccelConfig(ladder=ladder, t_ctrl=1000, enable_curvature=False,
                         **tac_kw)
    ctl = init_control(grouping.num_layers, tac)
    if codes is not None:
        ctl = ctl._replace(codes=jnp.asarray(codes, jnp.int32))
    comp = init_compute(task, params, grouping, ctl, tac)
    return task, params, grouping, tac, ctl, comp


# ======================================================================
# parity grid: fused vs reference, one step from a SHARED state
# (multi-step trajectories diverge chaotically from last-ulp reduction-
# order differences in the global norm; per-step parity is the invariant)
# ======================================================================
@pytest.mark.parametrize("optname", ["sgdm", "adamw"])
@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("grad_clip", [0.0, 1.0])
@pytest.mark.parametrize("compute", [jnp.float32, jnp.bfloat16])
def test_fused_matches_reference_one_step(optname, nesterov, grad_clip,
                                          compute):
    if optname == "adamw" and nesterov:
        pytest.skip("nesterov is an sgdm knob")
    opt = (sgdm(0.9, weight_decay=1e-4, nesterov=nesterov)
           if optname == "sgdm" else adamw(weight_decay=1e-2))
    task, params, grouping, tac, ctl, comp = _fixture(opt, compute)
    sched = lambda s: jnp.asarray(1e-2)
    ref_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       grad_clip=grad_clip,
                                       fused_update=False))
    fus_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       grad_clip=grad_clip,
                                       fused_update=True))
    batch = LMTaskStream(64, 32, 8, seed=1).batch(0)
    ref, mr = ref_step(TrainState(params, {}, opt.init(params), ctl), batch)
    fus, mf = fus_step(TrainState(params, {}, opt.init(params), ctl, comp),
                       batch)
    np.testing.assert_array_equal(np.asarray(mr["loss"]),
                                  np.asarray(mf["loss"]))
    assert bool(mr["grads_finite"]) and bool(mf["grads_finite"])
    # gradient-derived state may differ at bf16-ulp level (~2^-8 relative):
    # the reference's QDQ backward rounds cotangents to the tier grid under
    # f32 compute, and the embedding-gather scatter-add accumulates in f32
    # on the reference vs the compute container on the fused path
    # (DESIGN.md §9); masters stay an order tighter (lr-scaled)
    g_rtol = 1e-2
    # atol covers lr x one-bf16-ulp drift of the embedding-gather cotangent
    # (scatter-add accumulates in f32 on the reference, in the compute
    # container on the fused path)
    for la, lb in zip(jax.tree.leaves(ref.params), jax.tree.leaves(fus.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=2e-5)
    for la, lb in zip(jax.tree.leaves(ref.opt_state),
                      jax.tree.leaves(fus.opt_state)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=g_rtol, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref.control.var_ema),
                               np.asarray(fus.control.var_ema),
                               rtol=2 * g_rtol, atol=1e-10)


@pytest.mark.parametrize("codes", [0, 2])
def test_fused_precision_code_extremes_one_step(codes):
    """Code 0 (fp8 QDQ, per-layer delayed-scaling amax on the fused path vs
    fresh per-tensor amax on the reference) and code 2 (no rounding below
    the container) both track the reference within the fp8 grid spacing."""
    opt = sgdm(0.9)
    task, params, grouping, tac, ctl, comp = _fixture(
        opt, jnp.bfloat16, codes=[codes] * 4)
    sched = lambda s: jnp.asarray(1e-2)
    ref_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       fused_update=False))
    fus_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       fused_update=True))
    batch = LMTaskStream(64, 32, 8, seed=1).batch(0)
    ref, mr = ref_step(TrainState(params, {}, opt.init(params), ctl), batch)
    fus, mf = fus_step(TrainState(params, {}, opt.init(params), ctl, comp),
                       batch)
    # code 0: the fused cast quantizes with PER-LAYER slab amax (the issue's
    # granularity) vs the reference's fresh per-tensor amax — weights land
    # on visibly different fp8 grids, so this bounds divergence rather than
    # matching grids; the grid math itself is bitwise-checked against
    # qdq_cast in test_apply_kernel_cast_matches_qdq_cast below
    tol = 5e-2 if codes == 0 else 1e-6
    np.testing.assert_allclose(float(mr["loss"]), float(mf["loss"]),
                               rtol=tol, atol=tol)
    for la, lb in zip(jax.tree.leaves(ref.params), jax.tree.leaves(fus.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-3, atol=5e-3 if codes == 0 else 2e-5)


@pytest.mark.parametrize("code", [0, 1, 2])
@pytest.mark.parametrize("ladder", ["tpu", "gpu"])
def test_apply_kernel_cast_matches_qdq_cast(code, ladder):
    """With lr=0 the apply kernel is a pure cast: the emitted compute copy
    must be bit-identical to ops.qdq_cast of the container-cast master at
    the SAME amax, for every code and both ladders."""
    from repro.kernels import ops
    from repro.kernels.fused_update import OptSpec, cast_scales
    from repro.kernels.layout import SLAB_M, SLAB_N
    R = SLAB_M
    p = jax.random.normal(KEY, (R, SLAB_N)) * 3
    g = jax.random.normal(jax.random.fold_in(KEY, 1), (R, SLAB_N))
    zeros = jnp.zeros((1, SLAB_M), jnp.float32)
    cw = p.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(cw)).reshape(1)
    scalars = jnp.asarray([1.0, 1.0, 1.0, 1.0], jnp.float32)
    p_new, _, _, cp, p_amax = ops.fused_apply(
        g, p, jnp.zeros_like(p), None, scalars, zeros.astype(jnp.int32),
        zeros, jnp.full((1, SLAB_M), code, jnp.int32),
        cast_scales(amax)[0] * jnp.ones((1, SLAB_M), jnp.float32),
        spec=OptSpec(kind="sgdm", momentum=0.9), ladder=ladder,
        cp_dtype=jnp.bfloat16, num_layers=1)
    np.testing.assert_array_equal(np.asarray(p_new), np.asarray(p))
    want = ops.qdq_cast(cw, jnp.asarray(code), ladder=ladder,
                        amax=amax[0]).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(cp, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_allclose(float(p_amax[0]), float(amax[0]), rtol=1e-6)


# ======================================================================
# bit-exact fp32 master trajectory (20 steps)
# ======================================================================
@dataclasses.dataclass
class _ToyTask(TrainTask):
    """Gather-free linear regression: with codes pinned at 2 the reference
    forward applies no rounding, so fused and reference compiled graphs see
    bit-identical weights every step. (Embedding GATHERS are excluded on
    purpose: their scatter-add cotangent accumulates in f32 on the
    reference path but in the compute container on the fused path — a
    documented one-ulp-level asymmetry, see DESIGN.md §9.)"""
    cfg: object = None
    compute_dtype = jnp.float32
    serves_tokens = False

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"lin": {"w": jax.random.normal(k1, (96, 32)) * 0.1,
                        "b": jnp.zeros((32,))},
                "head": {"w": jax.random.normal(k2, (32, 8)) * 0.1}}, {}

    def loss(self, params, aux_state, batch, codes, qdq_fn):
        if qdq_fn is not None:
            keys = sorted(params.keys())
            params = {k: jax.tree.map(lambda w: qdq_fn(w, codes[i]),
                                      params[k])
                      for i, k in enumerate(keys)}
        h = jnp.tanh(batch["x"] @ params["lin"]["w"] + params["lin"]["b"])
        y = h @ params["head"]["w"]
        loss = jnp.mean(jnp.square(y - batch["y"]))
        return loss, aux_state, {"loss": loss}

    def grouping(self, params):
        return flat_grouping(params)


def _toy_batch(i):
    k = jax.random.fold_in(KEY, i)
    x = jax.random.normal(k, (16, 96))
    return {"x": x, "y": jnp.sum(x, axis=1, keepdims=True) * jnp.ones((1, 8))}


@pytest.mark.parametrize("optname", ["sgdm", "adamw"])
def test_bit_exact_master_trajectory_20_steps(optname):
    # a power-of-two weight decay makes ``wd * p`` exact, so the update is
    # bit-exact whether or not the compiler fuses ``g + wd * p`` into one
    # multiply-add (XLA:CPU contracts it in the reference fusion but not in
    # the kernel body; DESIGN.md §9)
    opt = (sgdm(0.9, weight_decay=2.0 ** -13) if optname == "sgdm"
           else adamw(weight_decay=1e-2))
    task = _ToyTask()
    params, _ = task.init(jax.random.PRNGKey(3))
    grouping = task.grouping(params)
    tac = TriAccelConfig(ladder="tpu", t_ctrl=1000, enable_curvature=False)
    ctl = init_control(grouping.num_layers, tac)
    ctl = ctl._replace(codes=jnp.full_like(ctl.codes, 2))
    comp = init_compute(task, params, grouping, ctl, tac)
    sched = lambda s: jnp.asarray(5e-3)
    ref_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       fused_update=False))
    fus_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       fused_update=True))
    ref = TrainState(params, {}, opt.init(params), ctl)
    fus = TrainState(params, {}, opt.init(params), ctl, comp)
    for i in range(20):
        ref, _ = ref_step(ref, _toy_batch(i))
        fus, _ = fus_step(fus, _toy_batch(i))
    if optname == "sgdm":
        # the paper's baseline optimizer: BIT-exact masters and momentum
        for la, lb in zip(jax.tree.leaves((ref.params, ref.opt_state)),
                          jax.tree.leaves((fus.params, fus.opt_state))):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    else:
        # adamw's bias-corrected step picks up ONE f32 ulp from XLA's
        # freedom in evaluating the rescaled divisions inside vs outside
        # the kernel body (m and v are still bitwise equal at the step of
        # first divergence); hold the 20-step trajectory to near-ulp level
        for la, lb in zip(jax.tree.leaves((ref.params, ref.opt_state)),
                          jax.tree.leaves((fus.params, fus.opt_state))):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=2e-6, atol=1e-6)


# ======================================================================
# cast_params is gone from the fused forward
# ======================================================================
def test_cast_params_eliminated_on_fused_path(monkeypatch):
    """The fused forward consumes the carried compute copy — tracing the
    fused step must never call cast_params, while the reference path still
    does (the PR 4 fold2d test's probe pattern, at the trace level)."""
    import repro.train.train_step as ts
    opt = sgdm(0.9)
    task, params, grouping, tac, ctl, comp = _fixture(opt, jnp.bfloat16)
    sched = lambda s: jnp.asarray(1e-3)
    batch = LMTaskStream(64, 32, 8, seed=0).batch(0)
    calls = []
    orig = ts.cast_params
    monkeypatch.setattr(ts, "cast_params",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    state = TrainState(params, {}, opt.init(params), ctl, comp)
    fused = make_train_step(task, tac, opt, grouping, sched,
                            fused_update=True)
    jax.make_jaxpr(fused)(state, batch)
    assert not calls, "fused path must not cast_params"
    reference = make_train_step(task, tac, opt, grouping, sched,
                                fused_update=False)
    jax.make_jaxpr(reference)(state, batch)
    assert calls, "reference path still casts"


def test_fused_zero_recompile_across_code_extremes():
    """Precision codes, lr scales and cast scales are runtime values on the
    fused path: forcing both code extremes dispatches into the SAME AOT
    executable (mirrors the PR 4 flash probe)."""
    from repro.train.trainer import Trainer, TrainerConfig
    task = LMTask(_tiny_lm(jnp.bfloat16))
    tac = TriAccelConfig(ladder="tpu", t_ctrl=2, enable_curvature=False,
                         enable_batch=False, mem_cap_bytes=8e9)
    tcfg = TrainerConfig(total_steps=4, seq_len=32, rungs=(2,),
                         log_every=1000, base_lr=1e-3)
    tr = Trainer(task, tac, tcfg)
    assert tr.fused
    tr.warm_rungs()
    assert tr.compile_count == 1
    tr.run(2)
    for codes in (0, 2):
        tr.state = tr.state._replace(control=tr.state.control._replace(
            codes=jnp.full_like(tr.state.control.codes, codes)))
        tr.run(1)
    assert tr.compile_count == 1
    assert all(np.isfinite(m["loss"]) for m in tr.metrics_log)


# ======================================================================
# accum trace-time guard (the silent broadcast_to duplication is gone)
# ======================================================================
def test_accum_uneven_split_raises():
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    with pytest.raises(ValueError, match="not divisible by accum"):
        split_microbatches(batch, 3)
    mb = split_microbatches(batch, 4)
    assert mb["tokens"].shape == (4, 2, 16)


def test_accum_uneven_split_raises_through_train_step():
    opt = sgdm(0.9)
    task, params, grouping, tac, ctl, comp = _fixture(opt)
    step = make_train_step(task, tac, opt, grouping,
                           lambda s: jnp.asarray(1e-3), accum=3)
    state = TrainState(params, {}, opt.init(params), ctl, comp)
    batch = LMTaskStream(64, 16, 8, seed=0).batch(0)    # 8 % 3 != 0
    with pytest.raises(ValueError, match="not divisible by accum"):
        jax.make_jaxpr(step)(state, batch)


def test_accum_even_split_fused_matches_reference():
    opt = sgdm(0.9)
    task, params, grouping, tac, ctl, comp = _fixture(opt)
    sched = lambda s: jnp.asarray(1e-2)
    batch = LMTaskStream(64, 16, 8, seed=2).batch(0)
    outs = {}
    for fused in (False, True):
        step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       accum=2, grad_clip=1.0,
                                       fused_update=fused))
        st = TrainState(params, {}, opt.init(params), ctl,
                        comp if fused else ())
        outs[fused], _ = step(st, batch)
    for la, lb in zip(jax.tree.leaves(outs[False].params),
                      jax.tree.leaves(outs[True].params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-4, atol=2e-6)


# ======================================================================
# non-finite containment + pre-fused checkpoint restore
# ======================================================================
def test_fused_stats_nonfinite_is_counted_not_propagated():
    """An inf/nan in one layer must be COUNTED (skip gate) without NaN-ing
    any layer's moments through the one-hot segment matmul — the moments
    come from the finite lanes, so the variance EMA survives overflow
    steps (the jnp reference permanently NaNs the offending layer)."""
    from repro.kernels import ops
    from repro.kernels.layout import SLAB_M, SLAB_N
    g = jnp.ones((2 * SLAB_M, SLAB_N))
    g = g.at[SLAB_M + 3, 7].set(jnp.inf).at[SLAB_M + 4, 9].set(jnp.nan)
    row_layer = jnp.concatenate([jnp.zeros((1, SLAB_M), jnp.int32),
                                 jnp.ones((1, SLAB_M), jnp.int32)])
    s, ss, mx, nf = ops.fused_stats(g, row_layer, 2)
    assert np.isfinite(np.asarray(s)).all() and np.isfinite(np.asarray(ss)).all()
    np.testing.assert_allclose(float(s[0]), SLAB_M * SLAB_N, rtol=1e-6)
    np.testing.assert_allclose(float(s[1]), SLAB_M * SLAB_N - 2, rtol=1e-6)
    assert float(nf[0]) == 0 and float(nf[1]) == 2
    assert float(mx[1]) == 1.0                   # absmax of FINITE lanes


def test_restore_pre_fused_checkpoint_reseeds_compute(tmp_path):
    """A checkpoint written by a reference-path (fused_update=False) run —
    i.e. one with no TrainState.compute leaves — must restore into a fused
    trainer, re-seeding the carry from the restored masters."""
    from repro.train.trainer import Trainer, TrainerConfig
    task = LMTask(_tiny_lm(jnp.bfloat16))
    tac = TriAccelConfig(ladder="tpu", t_ctrl=4, enable_curvature=False,
                         enable_batch=False, mem_cap_bytes=8e9)
    mk = lambda **kw: TrainerConfig(total_steps=4, seq_len=16, rungs=(4,),
                                    ckpt_dir=str(tmp_path), ckpt_every=100,
                                    log_every=1000, base_lr=1e-2, **kw)
    ref_tr = Trainer(task, tac, mk(fused_update=False))
    assert not ref_tr.fused
    ref_tr.run(3)
    ref_tr.ckpt.wait()

    fus_tr = Trainer(task, tac, mk())
    assert fus_tr.fused
    assert fus_tr.maybe_restore() == 3
    for a, b in zip(jax.tree.leaves(ref_tr.state.params),
                    jax.tree.leaves(fus_tr.params_tree())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert isinstance(fus_tr.state.compute, dict)    # re-seeded carry
    fus_tr.ckpt = None
    fus_tr.run(2)                                    # and it trains on
    assert np.isfinite(float(fus_tr.state.control.loss_scale))


# ======================================================================
# absmax-table reuse: serving ladder + qdq_cast(amax=...)
# ======================================================================
def test_serving_amax_tree_feeds_tier_params():
    from repro.kernels import ops
    from repro.serve.engine import tier_params
    from repro.train.trainer import Trainer, TrainerConfig
    task = LMTask(_tiny_lm(jnp.bfloat16))
    tac = TriAccelConfig(ladder="tpu", t_ctrl=1000, enable_curvature=False,
                         enable_batch=False, mem_cap_bytes=8e9)
    tr = Trainer(task, tac, TrainerConfig(total_steps=2, seq_len=32,
                                          rungs=(2,), log_every=1000))
    tr.run(2)
    amax_tree = tr.serving_amax_tree()
    assert amax_tree is not None
    # eval/export boundary: masters leave slab form exactly here
    params = tr.params_tree()
    # the carried table bounds every leaf's true absmax (it is the max over
    # the leaf's layer, measured on the container-cast master)
    for (path, leaf), amax in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree.leaves(amax_tree)):
        true = float(jnp.max(jnp.abs(leaf.astype(jnp.bfloat16)
                                     .astype(jnp.float32))))
        assert float(amax) >= true - 1e-6, jax.tree_util.keystr(path)
    # tier-0 weights built from the table == qdq_cast with the same amax
    got = tier_params(params, 0, "tpu", amax_tree=amax_tree)
    for (leaf, amax, want) in zip(jax.tree.leaves(params),
                                  jax.tree.leaves(amax_tree),
                                  jax.tree.leaves(got)):
        direct = ops.qdq_cast(leaf.astype(jnp.float32),
                              jnp.asarray(0, jnp.int32), ladder="tpu",
                              amax=amax).astype(jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(want, np.float32),
                                      np.asarray(direct, np.float32))


# ======================================================================
# slab residency (DESIGN.md §10): bit-exact vs the pack-per-step path,
# zero pack/unpack copies in the jaxpr, sharded-path parity
# ======================================================================
class _ToyTaskBF16(_ToyTask):
    compute_dtype = jnp.bfloat16


def _toy_states(optname, task=None):
    opt = (sgdm(0.9, weight_decay=1e-4) if optname == "sgdm"
           else adamw(weight_decay=1e-2))
    task = task if task is not None else _ToyTask()
    params, _ = task.init(jax.random.PRNGKey(3))
    grouping = task.grouping(params)
    tac = TriAccelConfig(ladder="tpu", t_ctrl=1000, enable_curvature=False)
    ctl = init_control(grouping.num_layers, tac)
    ctl = ctl._replace(codes=jnp.full_like(ctl.codes, 2))
    comp = init_compute(task, params, grouping, ctl, tac)
    return opt, task, params, grouping, tac, ctl, comp


@pytest.mark.parametrize("optname", ["sgdm", "adamw"])
def test_resident_bit_exact_vs_packed_20_steps(optname):
    """The resident step (slabs in, slabs out; gradient cotangent born in
    slab layout) must reproduce the PR-5 pack-per-step trajectory: sgdm
    BIT-exact, adamw to one f32 ulp, over 20 steps — including the carried
    compute copy."""
    from repro.kernels.layout import slab_view
    from repro.train.train_step import pack_state, unpack_state
    opt, task, params, grouping, tac, ctl, comp = _toy_states(optname)
    sched = lambda s: jnp.asarray(5e-3)
    packed_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                          fused_update=True))
    res_step = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                       fused_update=True,
                                       resident_params=params))
    view = slab_view(params, grouping)
    pk = TrainState(params, {}, opt.init(params), ctl, comp)
    rs = pack_state(view, TrainState(params, {}, opt.init(params), ctl,
                                     comp), task.compute_dtype)
    for i in range(20):
        pk, mp = packed_step(pk, _toy_batch(i))
        rs, mr = res_step(rs, _toy_batch(i))
        np.testing.assert_array_equal(np.asarray(mp["loss"]),
                                      np.asarray(mr["loss"]))
    un = unpack_state(view, rs, params)
    pairs = zip(jax.tree.leaves((pk.params, pk.opt_state)),
                jax.tree.leaves((un.params, un.opt_state)))
    if optname == "sgdm":
        for la, lb in pairs:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    else:
        for la, lb in pairs:
            np.testing.assert_allclose(np.asarray(la, np.float32),
                                       np.asarray(lb, np.float32),
                                       rtol=2e-6, atol=1e-7)
    # carried compute copy: identical next-step weights
    cp_res = view.unpack(rs.compute["slab"], like=pk.compute["tree"])
    for la, lb in zip(jax.tree.leaves(pk.compute["tree"]),
                      jax.tree.leaves(cp_res)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))
    np.testing.assert_array_equal(np.asarray(pk.compute["p_amax"]),
                                  np.asarray(rs.compute["p_amax"]))


def test_resident_jaxpr_zero_pack_unpack_copies():
    """The resident step's jaxpr contains ZERO per-step pack/unpack copies
    of master/moments: no f32 slab concatenates and (with a bf16 compute
    container, so the forward unpack is not f32 either) no f32 slab
    slices. The pack-per-step path has both. Counting is done by the
    shared analysis walker (repro.analysis.slab_copy_counts) — the same
    machinery rule R1 runs over every config."""
    from repro.analysis import slab_copy_counts
    from repro.kernels.layout import slab_view
    from repro.train.train_step import pack_state
    opt, task, params, grouping, tac, ctl, comp = _toy_states(
        "sgdm", task=_ToyTaskBF16())
    sched = lambda s: jnp.asarray(5e-3)
    view = slab_view(params, grouping)
    batch = _toy_batch(0)

    res_step = make_train_step(task, tac, opt, grouping, sched,
                               fused_update=True, resident_params=params)
    rs = pack_state(view, TrainState(params, {}, opt.init(params), ctl,
                                     comp), task.compute_dtype)
    res_counts = slab_copy_counts(jax.make_jaxpr(res_step)(rs, batch),
                                  view.rows)
    assert res_counts == {"concatenate": 0, "slice": 0}, res_counts

    packed_step = make_train_step(task, tac, opt, grouping, sched,
                                  fused_update=True)
    pk = TrainState(params, {}, opt.init(params), ctl, comp)
    pk_counts = slab_copy_counts(jax.make_jaxpr(packed_step)(pk, batch),
                                 view.rows)
    assert pk_counts["concatenate"] > 0 and pk_counts["slice"] > 0, pk_counts


def test_resident_requires_fused_and_floating():
    opt, task, params, grouping, tac, ctl, comp = _toy_states("sgdm")
    sched = lambda s: jnp.asarray(5e-3)
    with pytest.raises(ValueError, match="resident"):
        make_train_step(task, tac, opt, grouping, sched, fused_update=False,
                        resident_params=params)
    bad = dict(params, idx={"i": jnp.arange(4, dtype=jnp.int32)})
    with pytest.raises(ValueError, match="floating"):
        make_train_step(task, tac, opt, grouping, sched, fused_update=True,
                        resident_params=bad)


@pytest.mark.slow
def test_resident_row_range_sharded_matches_single_shard():
    """Row-range sharding over a 2-device data mesh (shard_map around both
    Pallas sweeps, cross-device segment combine) matches the single-shard
    oracle. Subprocess: needs XLA_FLAGS device-count forcing before jax
    init."""
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        assert jax.device_count() == 2
        from jax.sharding import Mesh
        from repro.core.controller import init_control
        from repro.core.precision import TriAccelConfig
        from repro.optim.optimizers import sgdm
        from repro.train.train_step import (TrainState, init_compute,
                                            make_train_step, pack_state,
                                            unpack_state)
        from repro.kernels.layout import slab_view
        import repro.launch.sharding as shd
        from test_fused_update import _ToyTask, _toy_batch

        mesh = Mesh(np.array(jax.devices()).reshape(2, 1), ("data", "model"))
        opt = sgdm(0.9, weight_decay=1e-4)
        task = _ToyTask()
        params, _ = task.init(jax.random.PRNGKey(3))
        grouping = task.grouping(params)
        tac = TriAccelConfig(ladder="tpu", t_ctrl=1000,
                             enable_curvature=False)
        ctl = init_control(grouping.num_layers, tac)
        ctl = ctl._replace(codes=jnp.full_like(ctl.codes, 2))
        comp = init_compute(task, params, grouping, ctl, tac)
        sched = lambda s: jnp.asarray(5e-3)

        view1 = slab_view(params, grouping)
        step1 = jax.jit(make_train_step(task, tac, opt, grouping, sched,
                                        fused_update=True,
                                        resident_params=params))
        st1 = pack_state(view1, TrainState(params, {}, opt.init(params),
                                           ctl, comp), task.compute_dtype)

        view2 = slab_view(params, grouping, shards=2)
        step2fn = make_train_step(task, tac, opt, grouping, sched,
                                  fused_update=True, resident_params=params,
                                  slab_shards=2, slab_mesh=mesh)
        st2 = pack_state(view2, TrainState(params, {}, opt.init(params),
                                           ctl, comp), task.compute_dtype)
        sh = shd.slab_sharding(mesh, 2)
        put = lambda x: jax.device_put(x, sh)
        st2 = TrainState(put(st2.params), st2.aux_state,
                         {k: (put(v) if k in ("mu", "m", "v") else v)
                          for k, v in st2.opt_state.items()},
                         st2.control,
                         {"slab": put(st2.compute["slab"]),
                          "p_amax": st2.compute["p_amax"]})
        with mesh, shd.activation_mesh(mesh):
            step2 = jax.jit(step2fn)
            for i in range(5):
                st1, m1 = step1(st1, _toy_batch(i))
                st2, m2 = step2(st2, _toy_batch(i))
        t1 = unpack_state(view1, st1, params)
        t2 = unpack_state(view2, jax.device_get(st2), params)
        for la, lb in zip(jax.tree.leaves((t1.params, t1.opt_state)),
                          jax.tree.leaves((t2.params, t2.opt_state))):
            np.testing.assert_allclose(np.asarray(la, np.float32),
                                       np.asarray(lb, np.float32),
                                       rtol=2e-6, atol=1e-7)
        print("SHARDED_RESIDENT_OK")
    """)
    # inherited flags may already force a device count (launch.dryrun sets
    # 512 at import time and pollutes the pytest process env) — strip any
    # prior forcing so ours is the only one the subprocess sees
    import re
    inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
    env = dict(os.environ,
               XLA_FLAGS=inherited
               + " --xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.path.dirname(__file__)]))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_RESIDENT_OK" in out.stdout


# ======================================================================
# stochastic rounding on the phase-2 compute cast
# ======================================================================
def _pure_cast_apply(p, sr, seed=3.0, cp_dtype=jnp.bfloat16):
    """lr=0 fused_apply = pure cast of the (unchanged) master."""
    from repro.kernels import ops
    from repro.kernels.fused_update import OptSpec
    from repro.kernels.layout import SLAB_M, SLAB_N
    zeros = jnp.zeros((p.shape[0] // SLAB_M, SLAB_M), jnp.float32)
    scalars = jnp.asarray([1.0, 1.0, 1.0, 1.0, seed], jnp.float32)
    _, _, _, cp, _ = ops.fused_apply(
        jnp.zeros_like(p), p, jnp.zeros_like(p), None, scalars,
        zeros.astype(jnp.int32), zeros,
        jnp.full_like(zeros, 2, jnp.int32), jnp.ones_like(zeros),
        spec=OptSpec(kind="sgdm", momentum=0.9), ladder="tpu",
        cp_dtype=cp_dtype, num_layers=1, sr=sr)
    return cp


def test_sr_disabled_is_bitexact_rtn():
    from repro.kernels.layout import SLAB_M, SLAB_N
    p = jax.random.normal(KEY, (SLAB_M, SLAB_N)) * 3
    cp = _pure_cast_apply(p, sr=False)
    np.testing.assert_array_equal(np.asarray(cp, np.float32),
                                  np.asarray(p.astype(jnp.bfloat16),
                                             np.float32))


def test_sr_rounds_to_bracketing_bf16_neighbors_deterministically():
    """SR output is always one of the two bf16 values bracketing the f32
    input; fixed (seed, step) is deterministic; a different seed picks
    different directions somewhere."""
    from repro.kernels.layout import SLAB_M, SLAB_N
    p = jnp.abs(jax.random.normal(KEY, (SLAB_M, SLAB_N))) + 0.1
    a = np.asarray(_pure_cast_apply(p, sr=True, seed=3.0), np.float32)
    b = np.asarray(_pure_cast_apply(p, sr=True, seed=3.0), np.float32)
    c = np.asarray(_pure_cast_apply(p, sr=True, seed=4.0), np.float32)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    # bracketing: truncation (toward zero = down for positives) or one ulp up
    bits = np.asarray(p, np.float32).view(np.uint32)
    lo = (bits & 0xFFFF0000).view(np.float32)
    hi = ((bits & 0xFFFF0000) + 0x10000).view(np.float32)
    assert np.all((a == lo) | (a == hi))
    assert (a == lo).any() and (a == hi).any()


def test_sr_unbiased_and_tighter_than_rtn_in_expectation():
    """Mean over seeds converges to the f32 value — closer than RTN's
    systematic bias on a fixed tensor."""
    from repro.kernels.layout import SLAB_M, SLAB_N
    p = jax.random.normal(jax.random.fold_in(KEY, 9),
                          (SLAB_M, SLAB_N)) * 0.37
    acc = np.zeros(p.shape, np.float64)
    n_seeds = 64
    for s in range(n_seeds):
        acc += np.asarray(_pure_cast_apply(p, sr=True, seed=float(s + 1)),
                          np.float32)
    sr_err = np.abs(acc / n_seeds - np.asarray(p, np.float64)).mean()
    rtn_err = np.abs(np.asarray(p.astype(jnp.bfloat16), np.float32)
                     - np.asarray(p, np.float32)).mean()
    assert sr_err < rtn_err * 0.5, (sr_err, rtn_err)


def test_sr_statically_disabled_for_f32_container():
    """SR only makes sense when the cast actually drops mantissa bits:
    with a f32 compute container fused_apply(sr=True) is the identity
    cast, bit-equal to sr=False."""
    from repro.kernels.layout import SLAB_M, SLAB_N
    p = jax.random.normal(KEY, (SLAB_M, SLAB_N))
    a = _pure_cast_apply(p, sr=True, cp_dtype=jnp.float32)
    b = _pure_cast_apply(p, sr=False, cp_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sr_trajectory_matches_rtn_when_disabled_end_to_end():
    """tac.stochastic_round=False (the default) leaves the resident step's
    20-step trajectory bit-identical to a step built before the SR knob
    existed (scalars padded with a zero seed); =True changes the compute
    copy but never the f32 masters' update rule inputs at step 0."""
    from repro.kernels.layout import slab_view
    from repro.train.train_step import pack_state
    opt, task, params, grouping, tac, ctl, comp = _toy_states(
        "sgdm", task=_ToyTaskBF16())
    sched = lambda s: jnp.asarray(5e-3)
    view = slab_view(params, grouping)
    tac_sr = dataclasses.replace(tac, stochastic_round=True)
    mk = lambda t: jax.jit(make_train_step(task, t, opt, grouping, sched,
                                           fused_update=True,
                                           resident_params=params))
    st0 = pack_state(view, TrainState(params, {}, opt.init(params), ctl,
                                      comp), task.compute_dtype)
    s_off, s_sr = st0, st0
    off_step, sr_step = mk(tac), mk(tac_sr)
    for i in range(3):
        s_off, _ = off_step(s_off, _toy_batch(i))
        s_sr, _ = sr_step(s_sr, _toy_batch(i))
    # masters at step 1 saw the same compute weights (step-0 cast is of the
    # same master; SR perturbs the cast), so trajectories diverge — but the
    # OFF run must match itself re-run (determinism) and differ from SR
    s_off2 = st0
    for i in range(3):
        s_off2, _ = off_step(s_off2, _toy_batch(i))
    np.testing.assert_array_equal(np.asarray(s_off.params),
                                  np.asarray(s_off2.params))
    assert (np.asarray(s_sr.compute["slab"], np.float32)
            != np.asarray(s_off.compute["slab"], np.float32)).any()


def test_serve_engine_accepts_amax_tree():
    from repro.serve.engine import ServeEngine
    task = LMTask(_tiny_lm(jnp.bfloat16))
    params, _ = split_params(task.init(jax.random.PRNGKey(0))[0])
    grouping = task.grouping(params)
    from repro.kernels.layout import slab_view
    from repro.kernels.fused_update import seed_compute
    view = slab_view(params, grouping)
    comp = seed_compute(view, params, jnp.ones((4,), jnp.int32), "tpu",
                        jnp.bfloat16)
    amax_tree = view.amax_tree(comp["p_amax"], params)
    eng = ServeEngine(task, params, total_len=16, prompt_len=4, rungs=(2,),
                      tiers=(0, 1), amax_tree=amax_tree)
    for leaf in jax.tree.leaves(eng.params_by_tier[0]):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
