"""Share of the traced training window in which the device was idle while
the host built or placed a batch (``train.data`` spans)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, ("train.data",))
