"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393
TOP/s int8, 16 GB HBM at 819 GB/s. A device that is not in the table is
an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/harness/peaks.py with its "
                       f"source")
    return PEAKS[device_kind][what]
