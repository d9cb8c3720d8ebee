"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

Copied from the program's ``repro.hw.CHIPS`` so that no change to the
program can move the yardstick. A kind not listed here is an error.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip links.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; known: {sorted(PEAKS)}") from None
