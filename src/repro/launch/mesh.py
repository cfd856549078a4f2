"""Production mesh construction and the per-chip peak rates.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so that
importing this module touches no jax device state — smoke tests see 1 CPU
device; only the dry-run process sets ``--xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import dataclasses

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests, degraded/elastic shapes), every axis Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peak rates (the roofline's denominators)."""

    flops_bf16: float           # FLOP/s
    hbm_bw: float               # HBM bytes/s
    hbm_bytes: int              # HBM capacity
    ici_bw_per_link: float      # bytes/s per interconnect link
    ici_links: int              # usable links per chip (2D torus)
    dcn_bw: float               # cross-pod bytes/s per host NIC (modeled)


V5E = "TPU v5 lite"             # ``jax.Device.device_kind`` of a TPU v5e

# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (4 links x 50 GB/s).  ``dcn_bw`` is not on that
# page: it is the multi-pod roofline's modeling assumption.
PEAKS = {
    V5E: ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 2**30,
        ici_bw_per_link=50e9, ici_links=4, dcn_bw=25e9,
    ),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peak rates of ``device_kind``; a kind with no published entry
    raises rather than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak rates for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}); add an entry with its source"
        ) from None
