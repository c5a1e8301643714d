import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost analysis + collective schedule.

MUST be the process entry point (the XLA_FLAGS line above runs before any
other import — jax locks the device count on first init).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out benchmarks/artifacts/dryrun
"""
import argparse
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, input_specs_for, skip_reason
from repro.core.grouping import encdec_grouping
from repro.core.precision import TriAccelConfig
from repro.launch.mesh import make_production_mesh
from repro.launch import sharding as shd
from repro.models.encdec import EncDecConfig
from repro.models.registry import get_arch_module, list_tasks
from repro.roofline.analysis import (HW, dominant_term, model_flops,
                                     roofline_terms)
from repro.roofline.hlo_parse import collective_bytes
from repro.roofline import costmodel as cm
from repro.train.schedules import warmup_cosine
from repro.train.serve import make_decode_fn, make_infer_fn, make_prefill_fn
from repro.train.train_step import TrainState, make_train_step, resolve_fused
from repro.optim.optimizers import sgdm
from repro.core.controller import init_control

SDS = jax.ShapeDtypeStruct


def _eval_shape_tree(fn, *args):
    return jax.eval_shape(fn, *args)


def param_count_active(cfg, pshape) -> float:
    """Active parameters for MODEL_FLOPS (MoE: shared + top_k routed only;
    enc-dec: each token traverses ~half the stack)."""
    total = sum(int(l.size) for l in jax.tree.leaves(pshape))
    if isinstance(cfg, EncDecConfig):
        return float(total) / 2.0
    stack = getattr(cfg, "stack", None)
    if stack is None or stack.moe is None:
        return float(total)
    moe = stack.moe
    # subtract the routed experts that are NOT active per token
    n_moe_layers = sum(n * sum(1 for bd in defs if bd.ffn == "moe")
                       for defs, n in stack.segments)
    per_expert = 3 * moe.d_model * moe.d_ff_expert
    inactive = (moe.num_experts - moe.top_k) * per_expert * n_moe_layers
    return float(total - inactive)


def build_lowered(arch: str, shape_name: str, mesh, accum: int = 1,
                  triaccel: bool = True, profile: str = "baseline",
                  capacity: float = None):
    mod = get_arch_module(arch)
    cfg = mod.config()
    if capacity is not None and getattr(getattr(cfg, "stack", None), "moe", None):
        import dataclasses as _dc
        moe = _dc.replace(cfg.stack.moe, capacity_factor=capacity)
        cfg = _dc.replace(cfg, stack=_dc.replace(cfg.stack, moe=moe))
    shape = SHAPES[shape_name]
    specs = input_specs_for(cfg, shape_name)
    key_sds = SDS((2,), jnp.uint32)

    from repro.train.task import task_for_config
    task = task_for_config(cfg)
    pshape_w, aux_shape = jax.eval_shape(task.init, key_sds)
    pvals_shape, paxes = (jax.tree.map(lambda p: p.value, pshape_w,
                                       is_leaf=lambda x: hasattr(x, "axes")),
                          jax.tree.map(lambda p: p.axes, pshape_w,
                                       is_leaf=lambda x: hasattr(x, "axes")))
    param_sh = shd.param_shardings(paxes, pvals_shape, mesh)
    n_active = param_count_active(cfg, pvals_shape)
    n_total = sum(int(l.size) for l in jax.tree.leaves(pvals_shape))
    chips = mesh.size
    info = {"params_total": n_total, "params_active": n_active}

    if shape.kind == "infer":
        # cache-free batched inference (the vision testbed's serve shape)
        infer = make_infer_fn(task)
        aux_sh = jax.tree.map(lambda _: shd.replicated(mesh), aux_shape)
        batch_sh = shd.batch_shardings(specs, mesh)
        with mesh, shd.activation_mesh(mesh):
            jitted = jax.jit(infer, in_shardings=(param_sh, aux_sh, batch_sh))
            lowered = jitted.lower(pvals_shape, aux_shape, specs)
        B = shape.global_batch
        info["model_flops"] = model_flops(n_active, B, "serve")
        # vision blocks are unrolled (no scan-over-layers), so XLA's
        # cost_analysis is trip-count-exact here: run_cell reads the roofline
        # inputs from the compiled module instead of the GEMM-enumeration model
        info["exec_costs"] = None
        from repro.train.paper_harness import activation_elems
        info["hbm_per_device"] = (n_total * 4.0
                                  + activation_elems(cfg) * 4.0 * B) / chips
        return lowered, info

    if shape.kind == "train":
        grouping = task.grouping(pvals_shape)
        tac = TriAccelConfig(ladder="tpu", dynamic_precision=triaccel)
        opt = sgdm(momentum=0.9)
        fused = resolve_fused(opt, tac)
        # slab residency mirrors the Trainer gate: fused + all-floating
        # params keep master/moments/compute as row-range-sharded slabs
        resident = fused and all(
            jnp.issubdtype(l.dtype, jnp.floating)
            for l in jax.tree.leaves(pvals_shape))
        dp_axes = shd.fsdp_axes(mesh)
        slab_shards = 1
        if resident and dp_axes:
            import numpy as _np
            slab_shards = int(_np.prod([mesh.shape[a] for a in dp_axes]))
        compute_sh = None
        if profile == "zero1":
            # ZeRO-1: bf16 compute copy replicated over the data axes (one
            # gather + one grad reduce-scatter per microstep at the cast)
            compute_sh = shd.param_shardings(paxes, pvals_shape, mesh,
                                             overrides={"embed": (),
                                                        "mlp2": ()})
        step_fn = make_train_step(task, tac, opt, grouping,
                                  warmup_cosine(3e-4, 100, 10000), accum=accum,
                                  compute_shardings=compute_sh,
                                  fused_update=fused,
                                  resident_params=pvals_shape if resident
                                  else None,
                                  slab_shards=slab_shards, slab_mesh=mesh)
        opt_shape = jax.eval_shape(opt.init, pvals_shape)
        opt_sh = shd.state_shardings_like(param_sh, opt_shape)
        ctl_shape = jax.eval_shape(lambda: init_control(grouping.num_layers, tac))
        ctl_sh = jax.tree.map(lambda _: shd.replicated(mesh), ctl_shape)
        compute_sds, compute_sh_tree = (), ()
        if fused:
            from repro.kernels.fused_update import compute_sds as _csds
            from repro.kernels.layout import slab_view
            view = slab_view(pvals_shape, grouping, shards=slab_shards)
            compute_sds = _csds(view, pvals_shape, grouping.num_layers,
                                task.compute_dtype, slab=resident)
            compute_sh_tree = {
                "tree": compute_sh if compute_sh is not None else param_sh,
                "p_amax": shd.replicated(mesh)}
        state_sds = TrainState(pvals_shape, {}, opt_shape, ctl_shape,
                               compute_sds)
        state_sh = TrainState(param_sh, {}, opt_sh, ctl_sh, compute_sh_tree)
        if resident:
            from repro.train.train_step import pack_state
            # abstract pack: slab-form SDS without materializing anything
            tree_compute = _csds(view, pvals_shape, grouping.num_layers,
                                 task.compute_dtype)
            state_sds = jax.eval_shape(
                lambda s: pack_state(view, s, task.compute_dtype),
                TrainState(pvals_shape, {}, opt_shape, ctl_shape,
                           tree_compute))
            slab_sh = shd.slab_sharding(mesh, slab_shards)
            rep = shd.replicated(mesh)
            opt_sh = {k: (slab_sh if k in ("mu", "m", "v") else rep)
                      for k in state_sds.opt_state}
            state_sh = TrainState(slab_sh, {}, opt_sh, ctl_sh,
                                  {"slab": slab_sh, "p_amax": rep})
        batch_sh = shd.batch_shardings(specs, mesh)
        with mesh, shd.activation_mesh(mesh):
            jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, specs)
        tokens = shape.global_batch * shape.seq_len
        info["model_flops"] = model_flops(n_active, tokens, "train")
        # executed FLOPs follow the kernel path: impl="flash" configs skip
        # fully-masked blocks in forward AND backward when the gate holds;
        # the update phase prices the fused slab sweep's 2-read model
        # (resident cells drop the pack/unpack assembly term to metadata)
        flags = cm.flash_skip_flags(cfg, shape.seq_len)
        ec = cm.train_costs(cfg, shape.global_batch, shape.seq_len, **flags)
        ec += cm.opt_traffic(n_total, slots=1, fused=fused, resident=resident)
        info["exec_costs"] = ec
        info["flash_fallback_reason"] = flags["reason"]
        info["update_phase_bytes"] = cm.update_phase_bytes(
            n_total, 1, fused, resident=resident)
        info["update_assembly_bytes"] = (
            cm.update_assembly_bytes(n_total, 1, resident=resident)
            if fused else 0.0)
        info["update_fused"] = fused
        info["update_resident"] = resident
        info["hbm_per_device"] = cm.hbm_estimate(
            cfg, "train", shape.global_batch, shape.seq_len, chips, accum,
            n_total)
        return lowered, info

    # --- serving paths use bf16 params, lowered through the task hooks ---
    pvals_bf16 = jax.tree.map(
        lambda s: SDS(s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        pvals_shape)
    if shape.kind == "prefill":
        prefill = make_prefill_fn(task)
        batch_sh = shd.batch_shardings(specs, mesh)
        with mesh, shd.activation_mesh(mesh):
            jitted = jax.jit(prefill, in_shardings=(param_sh, batch_sh))
            lowered = jitted.lower(pvals_bf16, specs)
        tokens = shape.global_batch * shape.seq_len
        info["model_flops"] = model_flops(n_active, tokens, "serve")
        flags = cm.flash_skip_flags(cfg, shape.seq_len)
        info["exec_costs"] = cm.prefill_costs(
            cfg, shape.global_batch, shape.seq_len, **flags)
        info["flash_fallback_reason"] = flags["reason"]
        info["hbm_per_device"] = cm.hbm_estimate(
            cfg, "prefill", shape.global_batch, shape.seq_len, chips, 1,
            n_total)
        return lowered, info

    # decode: one token against a seq_len cache
    B, S = shape.global_batch, shape.seq_len
    cache_shape = jax.eval_shape(
        lambda: task.init_cache({"tokens": SDS((B, 1), jnp.int32)}, S))
    cache_sh = shd.cache_shardings(cache_shape, mesh)
    decode = make_decode_fn(task)
    tok_sds = SDS((B,), jnp.int32)
    idx_sds = SDS((), jnp.int32)
    with mesh, shd.activation_mesh(mesh):
        jitted = jax.jit(decode,
                         in_shardings=(param_sh, cache_sh,
                                       shd.batch_shardings({"token": tok_sds}, mesh)["token"],
                                       shd.replicated(mesh)),
                         donate_argnums=(1,))
        lowered = jitted.lower(pvals_bf16, cache_shape, tok_sds, idx_sds)
    info["model_flops"] = model_flops(n_active, B, "serve")
    info["exec_costs"] = cm.decode_costs(cfg, B, S)
    info["hbm_per_device"] = cm.hbm_estimate(cfg, "decode", B, S, chips, 1,
                                             n_total)
    return lowered, info


# encoder-decoder grouping moved to repro.core.grouping; old name kept for
# existing importers
_encdec_grouping = encdec_grouping


def run_cell(arch, shape_name, mesh_kind, hw=HW(), out_dir=None,
             triaccel=True, profile: str = "baseline", accum=None,
             capacity=None):
    import re as _re
    tp = _re.search(r"_?tp(\d+)$", profile)
    if tp:
        # same 256/512 chips, model-parallel degree remapped 16 -> N
        n = int(tp.group(1))
        shape = ((2, 256 // n, n) if mesh_kind == "multi"
                 else (256 // n, n))
        axes = (("pod", "data", "model") if mesh_kind == "multi"
                else ("data", "model"))
        mesh = jax.make_mesh(
            shape, axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size
    mod = get_arch_module(arch)
    reason = skip_reason(mod.config(), shape_name,
                         getattr(mod, "SKIP_SHAPES", {}))
    if reason is not None:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": reason,
               "profile": profile}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = os.path.join(out_dir,
                              f"{arch}__{shape_name}__{mesh_kind}.json")
            with open(fn, "w") as f:
                json.dump(res, f, indent=1)
        return res
    if accum is None:
        accum = getattr(mod, "DRYRUN_ACCUM", {}).get(shape_name, 1)
    t0 = time.time()
    res = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": chips, "accum": accum, "profile": profile}
    try:
        base_profile = "zero1" if "zero1" in profile else "baseline"
        lowered, info = build_lowered(arch, shape_name, mesh, accum=accum,
                                      triaccel=triaccel, profile=base_profile,
                                      capacity=capacity)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        res.update(info)
        res["lower_s"] = round(t1 - t0, 1)
        res["compile_s"] = round(t2 - t1, 1)

        # raw XLA numbers for reference (loop bodies counted ONCE — see
        # roofline/costmodel.py for why these are not the roofline inputs)
        mem = compiled.memory_analysis()
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "peak_memory_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    res[f"xla_{k}"] = int(v)
        # measured executable footprint (the §3.3 controllers' signal) next
        # to the analytic model, so calibration drift is visible per cell
        from repro.core.batch_scaler import measured_exe_bytes
        meas = measured_exe_bytes(compiled)
        res["measured_bytes_per_device"] = meas
        res["modeled_over_measured"] = (
            round(info["hbm_per_device"] / meas, 3) if meas else None)
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        res["xla_flops_body_once"] = float(cost.get("flops", 0.0)) if cost else 0.0
        res["xla_bytes_body_once"] = float(cost.get("bytes accessed", 0.0)) \
            if cost else 0.0

        # collective schedule: trip-count-expanded parse of the SPMD HLO
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        coll_dev = float(sum(coll.values()))

        # analytic executed flops / HBM traffic (global), then per device.
        # Scan-free cells (vision infer) carry exec_costs=None: there XLA's
        # cost_analysis is trip-count-exact and is used directly.
        shape = SHAPES[shape_name]
        ecosts = info["exec_costs"]
        if ecosts is not None:
            flops_dev = ecosts.flops / chips
            bytes_dev = ecosts.bytes / chips
            flops_global = ecosts.flops
        else:
            flops_dev = res["xla_flops_body_once"]
            bytes_dev = res["xla_bytes_body_once"]
            flops_global = flops_dev * chips
        res["flops_per_device"] = flops_dev
        res["bytes_per_device"] = bytes_dev
        res["collective_bytes_per_device"] = coll_dev
        res["collectives"] = coll
        terms = roofline_terms(flops_dev, bytes_dev, coll_dev, hw)
        res.update(terms)
        res["dominant"] = dominant_term(terms)
        mf = info.get("model_flops", 0.0)
        res["useful_flop_ratio"] = mf / flops_global if flops_global else None
        # per-device HBM: analytic (params/opt/grads + activations + caches)
        res["hbm_per_device_bytes"] = info["hbm_per_device"]
        res["fits_hbm"] = bool(info["hbm_per_device"] < hw.hbm_bytes)
        res["status"] = "ok"
    except Exception as e:  # noqa
        res["status"] = "error"
        res["error"] = f"{type(e).__name__}: {e}"
        res["traceback"] = traceback.format_exc()[-4000:]
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if profile == "baseline" else f"__{profile}"
        fn = os.path.join(out_dir,
                          f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(res, f, indent=1, default=str)
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--no-triaccel", action="store_true",
                    help="lower the static-bf16 step instead of the "
                         "Tri-Accel dynamic-precision step")
    ap.add_argument("--profile", default="baseline",
                    help="weight-sharding / mesh-mapping profile: baseline, "
                         "zero1, tpN, zero1_tpN (N = model-parallel degree)")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--capacity", type=float, default=None,
                    help="override MoE capacity factor")
    args = ap.parse_args()

    archs = list_tasks() if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                r = run_cell(arch, shape_name, mesh_kind, out_dir=args.out,
                             triaccel=not args.no_triaccel,
                             profile=args.profile, accum=args.accum,
                             capacity=args.capacity)
                line = {k: r.get(k) for k in
                        ("arch", "shape", "mesh", "status", "lower_s",
                         "compile_s", "flops_per_device",
                         "collective_bytes_per_device", "dominant",
                         "hbm_per_device_bytes", "measured_bytes_per_device",
                         "modeled_over_measured", "fits_hbm")}
                print(json.dumps(line), flush=True)
                if r["status"] == "error":
                    failures += 1
                    print(r["error"], file=sys.stderr, flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
