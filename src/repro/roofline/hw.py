"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A roofline is only as good as the peaks it divides by, so every consumer
asks for the peaks of a named device kind (:func:`peaks`) or of the device
it runs on (:func:`device_peaks`).  A TPU kind missing from :data:`PEAKS`
is an error, never a silent v5e default.

Source for ``"TPU v5 lite"`` (the ``device_kind`` JAX reports for a TPU
v5e): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect
per chip (four links).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Per-chip peak rates and capacity."""

    name: str
    flops_bf16: float      # FLOP/s
    ops_int8: float        # OP/s
    hbm_bw: float          # bytes/s
    hbm_bytes: float       # capacity
    ici_link_bw: float     # bytes/s per link (~ per-chip injection for ring
                           # collectives on one axis)


V5E_KIND = "TPU v5 lite"

PEAKS: dict[str, ChipPeaks] = {
    V5E_KIND: ChipPeaks(
        name="TPU v5e",
        flops_bf16=197e12,
        ops_int8=393e12,
        hbm_bw=819e9,
        hbm_bytes=16 * 2**30,
        ici_link_bw=1600e9 / 8 / 4,
    ),
}


class UnknownDeviceError(KeyError):
    """A device kind with no entry in :data:`PEAKS`."""


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises :class:`UnknownDeviceError` for a
    kind the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"repro.roofline.hw.PEAKS (have {sorted(PEAKS)})"
        ) from None


def device_peaks(device) -> ChipPeaks | None:
    """Peaks of a ``jax.Device``: ``None`` off-TPU (no published roofline
    applies to a host CPU), the table entry on a TPU, and
    :class:`UnknownDeviceError` for a TPU kind the table lacks."""
    if device.platform != "tpu":
        return None
    return peaks(device.device_kind)
