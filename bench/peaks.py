"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A copy of ``DEVICE_PEAKS`` in ``repro/launch/roofline.py``.  TPU v5e:
Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.  No
integer vector (VPU) peak is published, so a codec kernel has no compute
term and its roofline is the HBM one.  A kind missing here is an error.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float  # FLOP/s per chip
    int8_ops: float  # OP/s per chip
    hbm_bytes: float  # bytes of HBM per chip
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per chip, all links


DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes=16e9,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,
    ),
}


def peaks(device_kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}"
        ) from None
