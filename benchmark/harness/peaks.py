"""Published peaks of the chips this benchmark knows, keyed by the
``device_kind`` JAX reports. An unknown kind is an error, never a default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s per chip."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud docs, TPU v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud docs, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            f"to benchmark/harness/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None
