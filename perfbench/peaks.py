"""Published peaks of the chips the benchmark measures on.

One table, keyed by ``jax.Device.device_kind``. A kind that is not here is an
error, never a default: a share of an assumed peak is not a number. The copy
in ``apex_tpu.utils.platform`` belongs to the program and may change with it;
this one is the yardstick's.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s per chip'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to perfbench/peaks.py with its "
            f"source") from None
