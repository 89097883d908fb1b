"""Published peaks of one chip, keyed by jax's `device_kind`.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page,
rows "Peak compute per chip (bf16)", "HBM2 capacity and bandwidth" and
"Interchip Interconnect BW": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect. jax reports the chip as
"TPU v5 lite"; "TPU v5e" is the marketing spelling of the same part.

A device that is not in the table is an error, never a default: a share of
a guessed peak is not a measurement. Add a row with its source to extend.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of `device_kind` (exact match, then longest prefix)."""
    if device_kind in PEAKS:
        return PEAKS[device_kind]
    for kind in sorted(PEAKS, key=len, reverse=True):
        if device_kind.lower().startswith(kind.lower()):
            return PEAKS[kind]
    raise KeyError(
        f"device_kind {device_kind!r} has no row in benchmark/peaks.py; add "
        f"its published peaks with their source before measuring on it")
