"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, the MXU's published peak
    hbm_bytes_per_s: float   # HBM bandwidth, bytes/s
    hbm_bytes: float         # HBM capacity, bytes
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
             source='Google Cloud documentation, "TPU v5e"')

#: device_kind -> peaks.  JAX names a v5e chip "TPU v5 lite".
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def for_device(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
