"""Seconds of set-up spent on step compiles that ran out of device memory
(``train.compile`` spans with outcome ``oom``): rungs the memory model
(core/batch_scaler.py) admits and the compiler does not fit."""
from bench import program_spans


def read(ctx):
    return program_spans.setup_seconds(ctx, "train.compile", outcome="oom")
