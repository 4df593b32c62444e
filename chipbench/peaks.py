"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.

A device that is not in the table is an error: a roofline share or a
utilisation against a guessed peak means nothing.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops_per_s": 197e12,
    "hbm_bytes_per_s": 819e9,
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "16 GB HBM at 819 GB/s per chip",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peak table entry for ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
