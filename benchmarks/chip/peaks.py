"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy of the table: a later change to the program
cannot move the yardstick.  A kind missing from the table is an error,
never a default.

Source for ``"TPU v5 lite"`` (the ``device_kind`` JAX reports for a TPU
v5e): Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Per-chip peak rates and capacity."""

    name: str
    flops_bf16: float   # FLOP/s
    ops_int8: float     # OP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: float    # bytes


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        name="TPU v5e",
        flops_bf16=197e12,
        ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16e9,
    ),
}


class UnknownDeviceError(KeyError):
    """A device kind the table does not list."""


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; :class:`UnknownDeviceError` for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r} "
            f"(have {sorted(PEAKS)})"
        ) from None
