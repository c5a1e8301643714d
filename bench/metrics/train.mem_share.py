"""Device memory the training step takes at the rung the window ran, as
the program measured it (``Trainer.measured_bytes``, XLA's memory analysis
of the step executable), over the device's limit: how full the elastic
batch keeps the chip. (The allocator's ``peak_bytes_in_use`` leaves the
executables' temporaries out on this runtime.)"""


def read(ctx):
    if ctx.get("kind") != "train" or not (ctx.get("bytes_limit")
                                          and ctx.get("step_bytes")):
        return None
    return 100.0 * ctx["step_bytes"] / ctx["bytes_limit"]
