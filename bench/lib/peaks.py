"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports. A chip that is not here is an error: the
benchmark never falls back to a default, and never runs on a CPU."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


class UnknownDevice(LookupError):
    pass


def lookup(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); the benchmark runs on those chips only"
        ) from None
