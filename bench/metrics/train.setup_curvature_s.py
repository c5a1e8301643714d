"""Seconds of set-up spent in the curvature refresh (``train.curvature``
spans: the eager warm refresh), on the host."""
from bench import program_spans


def read(ctx):
    return program_spans.setup_seconds(ctx, "train.curvature")
