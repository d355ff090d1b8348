"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device that is not in the table is an error:
a roofline share against a guessed peak is no measurement."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, TPU v5e",
    ),
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/lib/peaks.py with their source (known: "
            f"{sorted(PEAKS)})") from None
