"""Published chip-to-chip (ICI) rates per chip, keyed by
``jax.Device.device_kind``, and the least time to combine the chips' pooled
partials.

TPU v5e: Google Cloud documentation, "TPU v5e" — 1,600 Gbps of interchip
interconnect bandwidth a chip.
"""

from __future__ import annotations

from bench import peaks as peaks_mod

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}


def ici_bytes_per_s(pk: dict) -> float:
    """The ICI rate of the chip whose published peaks are ``pk``."""
    for kind, p in peaks_mod.PEAKS.items():
        if p == pk and kind in ICI_BYTES_PER_S:
            return ICI_BYTES_PER_S[kind]
    raise KeyError(f"no published ICI rate for peaks {pk}")


def reduce_bytes(batch: int, tables: int, dim: int, chips: int) -> float:
    """Bytes each chip must at least receive to sum ``chips`` float32 pooled
    partials of (batch, tables, dim): the other chips' shares of its own
    part of the result, ``(chips - 1) / chips`` of one partial."""
    return (chips - 1) / chips * batch * tables * dim * 4


def least_reduce_s(batch: int, tables: int, dim: int, chips: int,
                   pk: dict) -> float:
    """``reduce_bytes`` at the chip's ICI rate."""
    return reduce_bytes(batch, tables, dim, chips) / ici_bytes_per_s(pk)
