"""Published peaks of the chips this benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.

No metric reads a peak yet: the kernels' roofline share waits for a byte
count that is right (PERF.md, Open questions). The table is here so that
the later metric cannot bring its own.
"""
PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind]
