"""The table of peaks, keyed by the exact ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (one chip: 197 TFLOP/s in
bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s). A device that is not
in the table is an error, never a default. Copied in idea from
``distkeras_tpu/obs/tape.py::BF16_PEAK_FLOPS`` (FLOP/s only there).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
