"""Published peaks of one chip, keyed by JAX's ``device_kind``.

An unknown kind is an error, never a default: a roofline share against
the wrong peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
