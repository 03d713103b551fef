"""Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.

Copied from ``bench.CHIP_PEAKS`` (sound; PERF.md lists the original for a
later PR to delete).  A device that is not here is an error, never a
default: a utilization over the wrong peak is a wrong number with a
right-looking name.
"""

CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,           # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  "bf16, 16 GB HBM2e at 819 GB/s per chip",
    },
}


def chip_peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; ``ValueError`` for a
    device the table lacks."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/lib/peaks.py (known: {sorted(CHIP_PEAKS)}); add "
            f"the row with its source before computing a utilization") \
            from None
