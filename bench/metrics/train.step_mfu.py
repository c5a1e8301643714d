"""Model FLOP/s utilisation of the training step: the forward and backward
operations one sample requires (no recomputation; bench/counts.py) times
the samples finished per second of the window, over the chips' bf16 peak."""
from bench.peaks import peak


def read(ctx):
    if ctx.get("kind") != "train" or ctx["window_s"] <= 0:
        return None
    rate = ctx["samples"] * ctx["flops_per_sample"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] *
                           peak(ctx["peak_kind"])["bf16_flops_per_s"])
