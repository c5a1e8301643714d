"""Roofline share of the flash attention kernels (forward, dQ, dK/dV) in
the training window: the causal operations and bytes every step's layers
require (bench/counts.py) against the kernels' summed device time."""
from bench import counts
from bench.peaks import peak, roofline_share

KERNELS = ("flash_attention_fwd", "flash_attention_bwd")


def read(ctx):
    red = ctx.get("trace")
    if red is None or ctx.get("kind") != "train":
        return None
    seconds, n = red.kernel_s(KERNELS)
    if n == 0 or seconds <= 0:
        return None
    s = counts.lm_shape(ctx["config"])
    seq = int(ctx["traffic"]["seq_len"])
    flops = sum(counts.flash_train_flops(r, s["heads"], s["head_dim"], seq,
                                         s["layers"]) for r in ctx["rungs"])
    nbytes = sum(counts.flash_train_bytes(r, s["heads"], s["kv"],
                                          s["head_dim"], seq, s["layers"])
                 for r in ctx["rungs"])
    return roofline_share(flops, nbytes, seconds, peak(ctx["peak_kind"]))
