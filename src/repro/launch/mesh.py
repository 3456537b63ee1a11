"""Production mesh construction and per-chip peaks.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from dataclasses import dataclass

from repro import compat

__all__ = ["make_production_mesh", "Peaks", "PEAKS", "PRODUCTION_KIND",
           "peaks"]

# device_kind of the chips the production mesh is made of
PRODUCTION_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks for the roofline."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    ici_bw: float  # B/s per inter-chip link


# keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect over 4 links (50 GB/s each)
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind without published peaks is an error,
    never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add them to "
                       "repro.launch.mesh.PEAKS with their source")
    return PEAKS[device_kind]
