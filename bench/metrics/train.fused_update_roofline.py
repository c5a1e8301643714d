"""Roofline share of the fused update sweeps (fused_stats, fused_apply) in
the training window: the bytes the slab-resident update must move every
step (bench/counts.py) at HBM peak, against both sweeps' summed device
time."""
from bench import counts
from bench.peaks import peak, roofline_share

KERNELS = ("fused_stats", "fused_apply")


def read(ctx):
    red = ctx.get("trace")
    if red is None or ctx.get("kind") != "train":
        return None
    seconds, n = red.kernel_s(KERNELS)
    if n == 0 or seconds <= 0:
        return None
    per_step = counts.fused_update_bytes(ctx["param_count"],
                                         ctx["grad_bytes"], ctx["grad_bytes"])
    return roofline_share(0.0, per_step * ctx["steps"], seconds,
                          peak(ctx["peak_kind"]))
