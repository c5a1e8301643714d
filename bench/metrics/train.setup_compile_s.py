"""Seconds of set-up spent compiling step executables (every
``train.compile`` span: lowering, compiling and reading the executable's
memory, failed compiles included)."""
from bench import program_spans


def read(ctx):
    return program_spans.setup_seconds(ctx, "train.compile")
