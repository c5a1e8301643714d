"""Seconds of set-up spent building the ``Trainer`` (its ``train.init``
span: init, placement, slab pack, memory model, data stream)."""
from bench import program_spans


def read(ctx):
    return program_spans.setup_seconds(ctx, "train.init")
