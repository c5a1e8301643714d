"""Grid steps the flash attention kernels (forward, dQ, dK/dV) launch, as
a share of those a dense walk over every (q-block, k-block) tile would
take: 100 x the launched steps over the dense ones, summed over the
``flash.grid`` records the program leaves in its span log
(``repro.obs``) each time it traces one of the kernel calls. A process
runs one cell, so every record in the log is this run's. None where
there is none: the kernel path was not reached, or the program records
no grid."""


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    recs = [r for r in obs.spans() if r.name == "flash.grid"]
    dense = sum(r.attrs["dense"] for r in recs)
    if not dense:
        return None
    return 100.0 * sum(r.attrs["launched"] for r in recs) / dense
