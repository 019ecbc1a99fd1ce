"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, not
a default."""

CHIP_PEAKS: dict[str, dict] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s, "
                  "1,600 Gbit/s ICI per chip",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       "add them to CHIP_PEAKS with their source")
    return CHIP_PEAKS[device_kind]
