"""Peak rates of each accelerator, keyed by the ``device_kind`` JAX reports.

Entry points price against the chip they run on through ``device_peaks``.
An accelerator that is not in the table is an error, not a default: a
roofline share against another chip's peaks is no measurement.  The CPU has
no peaks of its own; programs there rehearse the v5e and price against it.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HWTarget:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    peak_ops_int8: float  # OP/s per chip
    hbm_bw: float  # B/s per chip
    ici_bw: float  # B/s per link
    hbm_bytes: float  # capacity per chip


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip (taken
# here as 4 links of 50 GB/s).
TPU_V5E = HWTarget(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_ops_int8=393e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16e9,
)

PEAKS: dict[str, HWTarget] = {"TPU v5 lite": TPU_V5E}


def device_peaks(device) -> HWTarget:
    """Peaks of ``device`` (a ``jax.Device``): its ``PEAKS`` entry, the v5e
    for the CPU, and ValueError for an accelerator not in the table."""
    if device.platform == "cpu":
        return TPU_V5E
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for {device.platform} device "
            f"{device.device_kind!r}: add it to repro.roofline.hw.PEAKS"
        ) from None
