"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` string JAX reports. A device missing from the table is
an error: a share of an unknown peak is no number at all."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture: peak compute and HBM per chip)",
    },
}


def peak(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises ``KeyError`` for a device
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row with its source to "
                       f"bench/peaks.py") from None


def roofline_share(flops: float, bytes_moved: float, seconds: float,
                   row: dict) -> float:
    """Percent of the roofline: the least time the chip could take for
    ``flops`` and ``bytes_moved`` (the larger of the two bounds) over the
    measured ``seconds``."""
    least = max(flops / row["bf16_flops_per_s"],
                bytes_moved / row["hbm_bytes_per_s"])
    return 100.0 * least / seconds
