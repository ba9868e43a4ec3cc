"""Production mesh construction and the device peak table.

Target: TPU v5e, 256 chips/pod.  Single-pod mesh is (16 data × 16 model);
multi-pod adds a leading pod axis (2 × 16 × 16 = 512 chips).  Defined as
functions so importing this module never touches jax device state — only
``launch/dryrun.py`` (which sets the host-device-count flag first) or a real
TPU launcher should call these.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.sharding import MeshCtx, auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} "
            f"({devices[0].device_kind if devices else 'none'})")
    return auto_mesh(shape, axes, devices=devices)


def make_meshctx(*, multi_pod: bool = False) -> MeshCtx:
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshCtx(mesh=mesh, batch_axes=batch_axes, model_axis="model")


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops_bf16: float          # FLOP/s
    hbm_bytes_per_s: float     # bytes/s
    ici_bytes_per_s: float     # chip-to-chip interconnect, bytes/s per chip


# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                               ici_bytes_per_s=1600e9 / 8),
}

# The chip the production meshes above are laid out for.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``; a kind without a published entry is an
    error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None
