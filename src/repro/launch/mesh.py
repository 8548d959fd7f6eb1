"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (never evaluated at import) so that
importing this module does not touch jax device state — smoke tests and
benchmarks must keep seeing the single real CPU device; only the dry-run
sets XLA_FLAGS for 512 placeholder host devices before first jax init.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one TPU generation."""
    flops_bf16: float       # FLOP/s (bf16 MXU)
    hbm_bw: float           # B/s
    ici_bw: float           # B/s per link
    vmem_bytes: int
    source: str


V5E = "TPU v5 lite"         # jax.Device.device_kind of a TPU v5e chip

# Peaks keyed by ``jax.Device.device_kind``, used by the roofline model
# and the parallelization pass's throughput estimator. A kind that is
# not here is an error (``chip_peaks``), never a default.
CHIP_PEAKS = {
    V5E: ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s chip-to-chip over four links
        ici_bw=50e9,
        # VMEM is not among the published figures: the resource
        # report's occupancy denominator, not a kernel's actual limit
        vmem_bytes=128 * 1024 * 1024,
        source='Google Cloud documentation, "TPU v5e" (system '
               'architecture: per-chip peaks)'),
}


def chip_peaks(device_kind: str | None) -> ChipPeaks:
    """The published peaks of a TPU ``device_kind``; raises for no
    kind and for a kind with no entry in ``CHIP_PEAKS``."""
    if device_kind is None:
        raise ValueError(
            "a TPU cost model needs the chip's device_kind "
            "(jax.Device.device_kind, e.g. Requirements.device_kind="
            f"{V5E!r}); none was given")
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device kind {device_kind!r}; "
            f"add it to launch.mesh.CHIP_PEAKS with its source "
            f"(known: {sorted(CHIP_PEAKS)})") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for smoke tests / CPU benchmarks."""
    return jax.make_mesh((1, 1), ("data", "model"))


def replica_devices(n_replicas: int):
    """Device placement for the sharded serving layer.

    With multiple local devices, replica i is pinned to device
    ``i % n_devices`` (its feeds are moved there with
    ``jax.device_put`` before dispatch).  With a single device the
    replicas are thread-backed and share it: placement is a no-op, so
    every entry is ``None``.
    """
    devs = jax.local_devices()
    if len(devs) <= 1:
        return [None] * n_replicas
    return [devs[i % len(devs)] for i in range(n_replicas)]
