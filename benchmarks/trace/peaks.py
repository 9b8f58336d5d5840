"""The one table of device peaks the benchmark's shares are taken of,
keyed by ``device_kind`` as JAX reports it. A device that is not here
is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM2e at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12,
                    "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"device kind {device_kind!r} is not in the benchmark's table "
            f"of peaks ({sorted(PEAKS)}): no share of a peak can be taken")
    return PEAKS[device_kind]
