"""Share of the traced training window in which the device was idle while
the host ran Tri-Accel's control loop: the code tick and rung choice
(``train.control``) or the curvature refresh (``train.curvature``)."""
from bench import program_spans


def read(ctx):
    return program_spans.idle_share(ctx, ("train.control",
                                          "train.curvature"))
