"""The jitted training step: loss -> grads -> Tri-Accel control -> update.

One compiled graph — shared by EVERY workload via the ``TrainTask``
interface (repro.train.task, DESIGN.md §1) — contains the whole §3.4
device-side loop:
  * per-layer QDQ precision emulation driven by control.codes (lax.switch),
  * fused per-layer gradient moment statistics (variance EMA inputs),
  * control-state update (EMA, code refresh on the t_ctrl cadence,
    dynamic loss scaling for the fp16 ladder),
  * curvature-scaled per-layer learning rates,
  * optimizer update over fp32 master params with non-finite-step skipping,
  * aux-state threading (e.g. BatchNorm running stats for vision tasks).

Two implementations of the post-backward *update phase* sit behind the
``fused_update`` gate (DESIGN.md §9):

  reference (``fused_update=False``) — the jnp oracle: six independent
  passes over the gradient footprint (finite check, global norm, clip,
  per-layer moments, ``opt.update``, ``apply_updates``) plus the next
  step's ``cast_params`` + in-loss QDQ.

  fused (default) — kernels.fused_update: a two-sweep Pallas slab kernel
  over the ``SlabView`` layout that reads each gradient tile exactly twice
  (stats, then apply) and emits the fp32 master write AND the next step's
  low-precision compute copy in the same tile. The compute copy (and the
  per-layer param-absmax table that prices its fp8 scales) is carried in
  ``TrainState.compute``, so the forward consumes it directly —
  ``cast_params`` and the in-loss QDQ switch disappear from the fused
  graph. Pallas runs the real kernel on TPU and interpret mode elsewhere,
  so the gate defaults ON wherever the optimizer publishes a kernel spec.

Gradient accumulation scans over microbatches (the memory-elastic batch
scaler selects the rung = microbatch size; the global batch and therefore
convergence semantics stay fixed unless the paper's true-B mode is chosen).
The per-device batch must split evenly into ``accum`` microbatches — an
uneven split raises at trace time (it used to be silently
``broadcast_to``-duplicated, inflating the effective batch).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.controller import ControlState, lr_scales, update_control
from repro.core.grouping import LayerGrouping
from repro.core.precision import TriAccelConfig, make_qdq_fn
from repro.kernels.fused_update import cast_scales, seed_compute
from repro.kernels.layout import SlabView, slab_view
from repro.models.encdec import EncDecConfig, encdec_loss
from repro.models.lm import lm_loss
from repro.optim.optimizers import Optimizer, apply_updates, global_norm


class TrainState(NamedTuple):
    params: Any          # fp32 master (tree; ONE (rows,512) slab if resident)
    aux_state: Any       # non-differentiated model state (BN stats); {} if none
    opt_state: Any
    control: ControlState
    #: fused-update carry: {"tree": next-step compute copy, "p_amax": (L,)}
    #: — {"slab": ..., "p_amax": ...} on the slab-resident path, () on the
    #: reference path (kept last + defaulted so 4-field constructors and
    #: old checkpoints stay valid)
    compute: Any = ()


def cast_params(params, dtype):
    return jax.tree.map(
        lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating) else p,
        params)


def make_loss_fn(cfg):
    if isinstance(cfg, EncDecConfig):
        return encdec_loss
    return lm_loss


def _tree_finite(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.asarray(True)
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]))


def split_microbatches(batch, accum: int):
    """(accum, B/accum, ...) microbatch stack for the grad-accum scan.

    Raises at trace time when the per-device batch does not divide evenly —
    the old path silently ``broadcast_to``-duplicated the whole batch into
    every microbatch, inflating the effective batch by ``accum``x."""
    def split(path, x):
        if x.ndim < 1:
            return jnp.broadcast_to(x[None], (accum,) + x.shape)
        if x.shape[0] % accum != 0:
            raise ValueError(
                f"batch leaf {jax.tree_util.keystr(path)} has leading dim "
                f"{x.shape[0]}, not divisible by accum={accum}; pick a "
                f"global batch that is a multiple of accum (the batch used "
                f"to be silently duplicated across microbatches here, "
                f"inflating the effective batch)")
        return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

    rest = {k: v for k, v in batch.items() if k != "mrope_positions"}
    mb0 = jax.tree_util.tree_map_with_path(split, rest)
    if "mrope_positions" in batch:
        mp = batch["mrope_positions"]          # batch rides on axis 1
        if mp.shape[1] % accum != 0:
            raise ValueError(
                f"mrope_positions batch dim {mp.shape[1]} is not divisible "
                f"by accum={accum}")
        mb0["mrope_positions"] = mp.reshape(
            (3, accum, mp.shape[1] // accum) + mp.shape[2:]
        ).transpose(1, 0, *range(2, mp.ndim + 1))
    return mb0


def _float_dtype(tree):
    for l in jax.tree.leaves(tree):
        if jnp.issubdtype(l.dtype, jnp.floating):
            return l.dtype
    return jnp.float32


def resolve_fused(opt: Optimizer, tac: TriAccelConfig) -> bool:
    """The ONE auto-resolution rule for the fused-update gate (shared by
    make_train_step, Trainer and launch.dryrun): the optimizer must publish
    a kernel spec, and dynamic precision must be active (the true-static
    baselines need the reference path's exact no-rounding semantics)."""
    return opt.spec is not None and tac.dynamic_precision


def _cast_codes(task, grouping, codes: jax.Array) -> jax.Array:
    """Codes the next-step CAST actuates: the loss applies QDQ only to the
    layers ``task.loss_codes`` exposes (the LM stack — embed/head
    pseudo-layers only get the container cast), so layers beyond that slice
    cast at code 2 (container dtype, no tier rounding)."""
    n_act = task.loss_codes(jnp.zeros((grouping.num_layers,),
                                      jnp.int32)).shape[0]
    if n_act >= grouping.num_layers:
        return codes
    return jnp.where(jnp.arange(grouping.num_layers) < n_act, codes, 2)


def init_compute(task, params, grouping, control: ControlState,
                 tac: TriAccelConfig):
    """Seed ``TrainState.compute`` for the fused path: the compute copy the
    first step's forward consumes + the per-layer param absmax table. A
    one-off jnp pass — every later copy is emitted in-tile by the kernel."""
    view = slab_view(params, grouping)
    return seed_compute(view, params, _cast_codes(task, grouping,
                                                  control.codes),
                        tac.ladder, task.compute_dtype)


_OPT_SLAB_KEYS = ("mu", "m", "v")


def pack_state(view: SlabView, state: TrainState,
               cp_dtype=None) -> TrainState:
    """Tree-form ``TrainState`` -> slab-resident form. Runs ONCE — trainer
    init and checkpoint restore — never inside the step."""
    p_slab = view.pack(state.params, jnp.float32)
    opt2 = {k: (view.pack(v, jnp.float32) if k in _OPT_SLAB_KEYS else v)
            for k, v in state.opt_state.items()}
    compute = state.compute
    if isinstance(compute, dict) and "tree" in compute:
        cd = cp_dtype if cp_dtype is not None else \
            _float_dtype(compute["tree"])
        compute = {"slab": view.pack(compute["tree"], cd),
                   "p_amax": compute["p_amax"]}
    return state._replace(params=p_slab, opt_state=opt2, compute=compute)


def unpack_state(view: SlabView, state: TrainState, params_like) -> TrainState:
    """Slab-resident ``TrainState`` -> tree form — the checkpoint/eval/
    export boundary representation, and the on-disk format pre-residency
    readers understand."""
    params = view.unpack(state.params, like=params_like)
    opt2 = {k: (view.unpack(v, like=params_like) if k in _OPT_SLAB_KEYS
                else v) for k, v in state.opt_state.items()}
    compute = state.compute
    if isinstance(compute, dict) and "slab" in compute:
        compute = {"tree": view.unpack(compute["slab"], like=params_like),
                   "p_amax": compute["p_amax"]}
    return state._replace(params=params, opt_state=opt2, compute=compute)


def make_train_step(task, tac: TriAccelConfig, opt: Optimizer,
                    grouping: LayerGrouping, schedule: Callable,
                    accum: int = 1, grad_clip: float = 0.0,
                    compute_shardings=None,
                    fused_update: Optional[bool] = None,
                    resident_params=None, slab_shards: int = 1,
                    slab_mesh=None):
    """Returns train_step(state, batch) -> (state, metrics) for any
    ``TrainTask``.

    ``compute_shardings`` (optional NamedSharding tree) pins the low-precision
    compute copy of the weights to a different layout than the fp32
    master — the ZeRO-1 profile replicates the compute copy over the data
    axes (one bf16 all-gather + one grad reduce-scatter per microstep at
    the cast boundary) instead of per-layer FSDP gathers + full-size grad
    all-reduces inside the layer scan.

    ``fused_update``: None (default) resolves to the fused Pallas update
    phase whenever the optimizer publishes a kernel spec (TPU kernel /
    interpret elsewhere); False pins the jnp reference path — the oracle
    the fused path is parity-tested against, and the home of trace-level
    features the kernel does not carry (true static precision, custom
    optimizers).

    ``resident_params`` (a params-shaped tree of arrays or
    ShapeDtypeStructs) switches the fused path to SLAB-RESIDENT state:
    the returned step consumes/produces a ``TrainState`` whose ``params``
    / ``opt_state`` moments / ``compute`` are single (rows, 512) slabs
    (see ``pack_state``/``unpack_state``), the loss differentiates
    directly w.r.t. the compute slab (the gradient cotangent is BORN in
    slab layout — no per-step ``view.pack``), and the master/moment slabs
    flow straight through the two Pallas sweeps: per-step HBM traffic hits
    the 2-read/2-write floor with ``update_assembly_bytes`` ~ 0.
    ``slab_shards`` > 1 partitions the slabs by row ranges aligned to the
    256-row block grid and runs each device's sweep over its local rows
    via shard_map on ``slab_mesh`` (per-layer stats combined with one
    cross-device segment reduce).
    """
    if fused_update is None:
        fused_update = resolve_fused(opt, tac)
    if fused_update and opt.spec is None:
        raise ValueError("fused_update=True needs an optimizer with a "
                         "kernel spec (repro.optim.optimizers.sgdm/adamw)")
    resident = resident_params is not None
    if resident and not fused_update:
        raise ValueError("slab-resident state requires the fused update "
                         "path (resident_params with fused_update=False)")
    if resident:
        for l in jax.tree.leaves(resident_params):
            if not jnp.issubdtype(l.dtype, jnp.floating):
                raise ValueError("slab residency needs an all-floating "
                                 "params tree (non-floating leaves have no "
                                 "slab rows to live in)")
        r_view = slab_view(resident_params, grouping, shards=slab_shards)
        r_like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            resident_params)
    qdq_fn = make_qdq_fn(tac)

    def loss_at(params32, aux_state, microbatch, codes, loss_scale):
        from repro.launch.sharding import constrain_tree_batch
        microbatch = constrain_tree_batch(microbatch)
        cp = cast_params(params32, task.compute_dtype)
        if compute_shardings is not None:
            cp = jax.tree.map(jax.lax.with_sharding_constraint, cp,
                              compute_shardings)
        total, new_aux, metrics = task.loss(cp, aux_state, microbatch,
                                            codes, qdq_fn)
        return total * loss_scale, (new_aux, metrics)

    def loss_fused(cp, aux_state, microbatch, loss_scale):
        """Fused-path forward: consumes the compute copy carried in
        ``TrainState.compute`` — no cast, no in-loss QDQ (both already
        applied in-tile by the previous step's apply kernel)."""
        from repro.launch.sharding import constrain_tree_batch
        microbatch = constrain_tree_batch(microbatch)
        if compute_shardings is not None:
            cp = jax.tree.map(jax.lax.with_sharding_constraint, cp,
                              compute_shardings)
        total, new_aux, metrics = task.loss(cp, aux_state, microbatch,
                                            None, None)
        return total * loss_scale, (new_aux, metrics)

    def loss_resident(cp_slab, aux_state, microbatch, loss_scale):
        """Resident-path forward: differentiates w.r.t. the compute SLAB.
        The in-forward unpack is pure placement (slice + reshape), so its
        AD transpose deposits the gradient cotangent directly into slab
        layout — the step never calls ``view.pack``."""
        from repro.launch.sharding import constrain_tree_batch
        microbatch = constrain_tree_batch(microbatch)
        cp = r_view.unpack(cp_slab, like=r_like)
        if compute_shardings is not None:
            cp = jax.tree.map(jax.lax.with_sharding_constraint, cp,
                              compute_shardings)
        total, new_aux, metrics = task.loss(cp, aux_state, microbatch,
                                            None, None)
        return total * loss_scale, (new_aux, metrics)

    def _grads(loss_fn, wrt, aux_state, batch, *extra):
        """value_and_grad over one batch or an accum-scan of microbatches."""
        if accum > 1:
            def micro(carry, mb):
                g_acc, aux = carry
                (_, (aux2, m)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(wrt, aux, mb, *extra)
                return (jax.tree.map(jnp.add, g_acc, g), aux2), m

            mb0 = split_microbatches(batch, accum)
            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), wrt)
            (grads, new_aux), mstack = jax.lax.scan(micro, (g0, aux_state),
                                                    mb0)
            metrics = jax.tree.map(
                lambda m: jnp.mean(m.astype(jnp.float32), axis=0)
                if jnp.issubdtype(m.dtype, jnp.floating) else m[-1], mstack)
            return grads, new_aux, metrics        # grads are the accum SUM
        (_, (new_aux, metrics)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(wrt, aux_state, batch, *extra)
        return grads, new_aux, metrics

    def _control_metrics(metrics, finite, control2, lr, gn):
        metrics = dict(metrics)
        metrics.update({
            "grads_finite": finite,
            "grad_norm": gn,                # global norm before clipping
            "loss_scale": control2.loss_scale,
            "lr": lr,
            "mean_code": jnp.mean(control2.codes.astype(jnp.float32)),
            "frac_low": jnp.mean((control2.codes == 0).astype(jnp.float32)),
            "frac_fp32": jnp.mean((control2.codes == 2).astype(jnp.float32)),
        })
        return metrics

    # ------------------------------------------------- reference path -----
    def reference_step(state: TrainState, batch):
        params32, aux_state, opt_state, control = state[:4]
        codes = task.loss_codes(control.codes)
        ls = control.loss_scale
        grads, new_aux, metrics = _grads(loss_at, params32, aux_state, batch,
                                         codes, ls)
        if accum > 1:
            grads = jax.tree.map(lambda g: g / accum, grads)

        grads = jax.tree.map(lambda g: (g.astype(jnp.float32) / ls), grads)
        finite = _tree_finite(grads)
        gn = global_norm(grads)
        if grad_clip > 0:
            clip = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
            grads = jax.tree.map(lambda g: g * clip, grads)

        # ---- Tri-Accel §3.4 device-side control update ----
        moments = grouping.moments(grads)
        control2 = update_control(control, moments, tac, finite)
        scales = lr_scales(control2, tac)                       # (L,)
        # rollback demotion (repro.resilience): a scalar carried in
        # ControlState, 1.0 unless a divergence rollback demoted it
        lr = schedule(control2.step) * control2.lr_demote
        lr_tree = grouping.broadcast(scales * lr, params32)

        updates, opt_state2 = opt.update(grads, opt_state, params32, lr_tree)
        new_params = apply_updates(params32, updates)
        # skip the step entirely on non-finite grads (fp16 ladder semantics)
        keep = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(finite, a, b), new, old)
        new_params = keep(new_params, params32)
        opt_state2 = keep(opt_state2, opt_state)
        new_aux = keep(new_aux, aux_state)

        metrics = _control_metrics(metrics, finite, control2, lr, gn)
        return TrainState(new_params, new_aux, opt_state2, control2,
                          state.compute), metrics

    # ----------------------------------------------------- fused path -----
    def fused_step(state: TrainState, batch):
        from repro.kernels import ops
        params32, aux_state, opt_state, control, compute = state
        if not isinstance(compute, dict):
            # 4-field caller: seed the carry in-graph (one cast_params-cost
            # pass; the returned state carries the kernel-emitted copy, so
            # every later step starts pre-cast)
            compute = init_compute(task, params32, grouping, control, tac)
        ls = control.loss_scale
        grads, new_aux, metrics = _grads(loss_fused, compute["tree"],
                                         aux_state, batch, ls)

        view = slab_view(params32, grouping)
        L = grouping.num_layers
        row_layer = view.row_blocks()
        g_slab = view.pack(grads, _float_dtype(grads))

        # phase 1: one gradient read -> per-layer stats
        sums, sumsqs, gmax, nonfinite = ops.fused_stats(g_slab, row_layer, L)

        # scalar combine (O(L)): unscale, finite gate, global clip, control
        denom = ls * accum
        s_l = sums / denom
        ss_l = sumsqs / jnp.square(denom)
        finite = jnp.sum(nonfinite) == 0
        gn = jnp.sqrt(jnp.sum(ss_l))
        if grad_clip > 0:
            clip = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
        else:
            clip = jnp.float32(1.0)
        moments = (s_l * clip, ss_l * jnp.square(clip), grouping.counts)
        control2 = update_control(control, moments, tac, finite)
        # rollback demotion (repro.resilience): a scalar carried in
        # ControlState, 1.0 unless a divergence rollback demoted it
        lr = schedule(control2.step) * control2.lr_demote
        lr_l = (lr_scales(control2, tac) * lr).astype(jnp.float32)

        if opt.spec.kind == "adamw":
            t = opt_state["t"] + 1
            tf = t.astype(jnp.float32)
            c1 = 1.0 - opt.spec.b1 ** tf
            c2 = 1.0 - opt.spec.b2 ** tf
            m_tree, v_tree = opt_state["m"], opt_state["v"]
        else:
            c1 = c2 = jnp.float32(1.0)
            m_tree, v_tree = opt_state["mu"], None
        scalars = jnp.stack([clip / denom, finite.astype(jnp.float32),
                             c1, c2, control2.step.astype(jnp.float32)]
                            ).astype(jnp.float32)

        # phase 2: final gradient read -> optimizer + master + next cast
        p_slab = view.pack(params32, jnp.float32)
        m_slab = view.pack(m_tree, jnp.float32)
        v_slab = view.pack(v_tree, jnp.float32) if v_tree is not None else None
        p_new, m_new, v_new, cp_slab, p_amax = ops.fused_apply(
            g_slab, p_slab, m_slab, v_slab, scalars, row_layer,
            view.gather_rows(lr_l),
            view.gather_rows(_cast_codes(task, grouping, control2.codes)),
            view.gather_rows(cast_scales(compute["p_amax"])),
            spec=opt.spec, ladder=tac.ladder, cp_dtype=task.compute_dtype,
            num_layers=L, sr=tac.stochastic_round)

        new_params = view.unpack(p_new, like=params32)
        if opt.spec.kind == "adamw":
            opt_state2 = {"m": view.unpack(m_new, like=m_tree),
                          "v": view.unpack(v_new, like=v_tree),
                          "t": jnp.where(finite, t, opt_state["t"])}
        else:
            opt_state2 = {"mu": view.unpack(m_new, like=m_tree)}
        new_aux = jax.tree.map(lambda a, b: jnp.where(finite, a, b),
                               new_aux, aux_state)
        compute2 = {"tree": view.unpack(cp_slab, like=params32),
                    "p_amax": p_amax}

        metrics = _control_metrics(metrics, finite, control2, lr, gn)
        # phase-1 absmax of the UNSCALED finite gradient lanes: the fp16
        # ladder's overflow-margin diagnostic (free — the stats sweep
        # already reduced it)
        metrics["grad_absmax"] = jnp.max(gmax) / denom
        return TrainState(new_params, new_aux, opt_state2, control2,
                          compute2), metrics

    # -------------------------------------------------- resident path -----
    # Row-range sharded sweeps: each device runs the Pallas kernels over its
    # local row range (shard_map — pallas_call is NOT partitioned by GSPMD),
    # and the per-layer phase-1 partials combine with ONE cross-device
    # segment reduce (psum/pmax over O(L) scalars).
    use_shmap = resident and slab_shards > 1 and slab_mesh is not None

    if use_shmap:
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import fsdp_axes
        dp = fsdp_axes(slab_mesh)
        rowdim = dp if len(dp) > 1 else dp[0]
        ssp = P(rowdim, None)                   # slabs + per-row metadata

        def _dp_index():
            idx = jax.lax.axis_index(dp[0])
            for a in dp[1:]:
                idx = idx * slab_mesh.shape[a] + jax.lax.axis_index(a)
            return idx

    def _stats(g_slab, row_layer, L):
        from repro.kernels import ops
        if not use_shmap:
            return ops.fused_stats(g_slab, row_layer, L)

        def body(g, rl):
            s, ss, mx, nf = ops.fused_stats(g, rl, L)
            return (jax.lax.psum(s, dp), jax.lax.psum(ss, dp),
                    jax.lax.pmax(mx, dp), jax.lax.psum(nf, dp))

        return shard_map(body, mesh=slab_mesh, in_specs=(ssp, ssp),
                         out_specs=(P(), P(), P(), P()),
                         check_rep=False)(g_slab, row_layer)

    def _apply(g_slab, p_slab, m_slab, v_slab, scalars, row_layer,
               lr_r, code_r, qs_r, L):
        from repro.kernels import ops
        kw = dict(spec=opt.spec, ladder=tac.ladder,
                  cp_dtype=task.compute_dtype, num_layers=L,
                  sr=tac.stochastic_round)
        if not use_shmap:
            return ops.fused_apply(g_slab, p_slab, m_slab, v_slab, scalars,
                                   row_layer, lr_r, code_r, qs_r, **kw)
        adam = opt.spec.kind == "adamw"

        def body(sc, g, p, m, rl, lr, cd, qs, *maybe_v):
            # decorrelate the SR stream across row shards: program_id
            # restarts at 0 on every device, so fold the shard index into
            # the seed (steps < 2^20 stay exact in the f32 seed slot)
            sc = sc.at[4].add(_dp_index().astype(jnp.float32) * 1048576.0)
            v = maybe_v[0] if adam else None
            p_n, m_n, v_n, cp, pmax = ops.fused_apply(
                g, p, m, v, sc, rl, lr, cd, qs, **kw)
            pmax = jax.lax.pmax(pmax, dp)
            if adam:
                return p_n, m_n, v_n, cp, pmax
            return p_n, m_n, cp, pmax

        in_specs = (P(),) + (ssp,) * 7 + ((ssp,) if adam else ())
        out_specs = (ssp, ssp) + ((ssp,) if adam else ()) + (ssp, P())
        args = (scalars, g_slab, p_slab, m_slab, row_layer, lr_r, code_r,
                qs_r) + ((v_slab,) if adam else ())
        outs = shard_map(body, mesh=slab_mesh, in_specs=in_specs,
                         out_specs=out_specs, check_rep=False)(*args)
        if adam:
            return outs
        p_n, m_n, cp, pmax = outs
        return p_n, m_n, None, cp, pmax

    def resident_step(state: TrainState, batch):
        p_slab, aux_state, opt_state, control, compute = state
        ls = control.loss_scale
        g_slab, new_aux, metrics = _grads(loss_resident, compute["slab"],
                                          aux_state, batch, ls)

        L = grouping.num_layers
        row_layer = r_view.row_blocks()

        # phase 1: one gradient read -> per-layer stats
        sums, sumsqs, gmax, nonfinite = _stats(g_slab, row_layer, L)

        denom = ls * accum
        s_l = sums / denom
        ss_l = sumsqs / jnp.square(denom)
        finite = jnp.sum(nonfinite) == 0
        gn = jnp.sqrt(jnp.sum(ss_l))
        if grad_clip > 0:
            clip = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
        else:
            clip = jnp.float32(1.0)
        moments = (s_l * clip, ss_l * jnp.square(clip), grouping.counts)
        control2 = update_control(control, moments, tac, finite)
        # rollback demotion (repro.resilience): a scalar carried in
        # ControlState, 1.0 unless a divergence rollback demoted it
        lr = schedule(control2.step) * control2.lr_demote
        lr_l = (lr_scales(control2, tac) * lr).astype(jnp.float32)

        if opt.spec.kind == "adamw":
            t = opt_state["t"] + 1
            tf = t.astype(jnp.float32)
            c1 = 1.0 - opt.spec.b1 ** tf
            c2 = 1.0 - opt.spec.b2 ** tf
            m_slab, v_slab = opt_state["m"], opt_state["v"]
        else:
            c1 = c2 = jnp.float32(1.0)
            m_slab, v_slab = opt_state["mu"], None
        scalars = jnp.stack([clip / denom, finite.astype(jnp.float32), c1,
                             c2, control2.step.astype(jnp.float32)]
                            ).astype(jnp.float32)

        # phase 2: the resident slabs flow straight through the kernel —
        # zero pack/unpack of master or moments anywhere in this step
        p_new, m_new, v_new, cp_slab, p_amax = _apply(
            g_slab, p_slab, m_slab, v_slab, scalars, row_layer,
            r_view.gather_rows(lr_l),
            r_view.gather_rows(_cast_codes(task, grouping, control2.codes)),
            r_view.gather_rows(cast_scales(compute["p_amax"])), L)

        if opt.spec.kind == "adamw":
            opt_state2 = {"m": m_new, "v": v_new,
                          "t": jnp.where(finite, t, opt_state["t"])}
        else:
            opt_state2 = {"mu": m_new}
        new_aux = jax.tree.map(lambda a, b: jnp.where(finite, a, b),
                               new_aux, aux_state)
        compute2 = {"slab": cp_slab, "p_amax": p_amax}

        metrics = _control_metrics(metrics, finite, control2, lr, gn)
        metrics["grad_absmax"] = jnp.max(gmax) / denom
        return TrainState(p_new, new_aux, opt_state2, control2,
                          compute2), metrics

    if resident:
        return resident_step
    return fused_step if fused_update else reference_step
