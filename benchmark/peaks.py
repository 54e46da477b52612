"""The benchmark's own table of peaks, keyed by JAX ``device_kind``.

Copied from capital_tpu/utils/tracing.SPECS so that no later PR can move the
yardstick by editing the program.  Source: Google Cloud TPU documentation,
system architecture pages "TPU v5e", "TPU v5p", "TPU v6e": peak bf16
compute per chip, HBM capacity and bandwidth.  A kind missing here is an
error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float  # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: float


_V5E = Peak(197e12, 819e9, 16e9)
_V5P = Peak(459e12, 2765e9, 95e9)
_V6E = Peak(918e12, 1640e9, 32e9)

PEAKS: dict[str, Peak] = {
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peak for device kind {device_kind!r} in "
            "benchmark/peaks.py") from None
