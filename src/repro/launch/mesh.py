"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any import).

Mesh semantics (HFL mapping, DESIGN.md §3):
  pod   (2)  — cloud tier: each pod is one edge-server cohort
  data  (16) — devices within an edge cohort (batch / FSDP axis)
  model (16) — tensor/expert parallel within a cohort
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``. Newer JAX defaults to
    ``Explicit`` axes, which put the sharding into each array's type and
    reject reshapes that split a sharded dimension (the CNN's max-pool
    reshape among them); this repo's code is written for the
    propagation-based ``Auto`` behaviour."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """1-device mesh with the same axis names (for CPU tests)."""
    shape = (1, 1, 1) if multi_pod else (1, 1)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def sweep_mesh(n_devices: int | None = None):
    """1-D ``Mesh(("lane",))`` over the local devices for lane-parallel
    sweeps (``SweepRunner(shard=True)``): seed lanes are embarrassingly
    parallel, so the sweep layer only ever shards the stacked lane axis.

    n_devices: use the first n local devices (default: all of them). On
    CPU the device count comes from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set BEFORE
    jax import — which is why this is a function, not a module constant
    (same rule as the production meshes above).
    """
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"sweep_mesh: asked for {n_devices} devices, only "
                f"{len(devs)} visible")
        devs = devs[:n_devices]
    return _auto_mesh((len(devs),), ("lane",), devices=devs)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU v5e docs ("TPU v5e" system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# of inter-chip interconnect (four links, so 50 GB/s per link direction).
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s
        "hbm_bw": 819e9,               # B/s
        "ici_bw_per_link": 50e9,       # B/s per link direction
        "source": "Google Cloud TPU v5e docs",
    },
}


def chip_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``. A kind that is not in
    ``PEAKS`` raises: a roofline against another chip's peaks is wrong,
    not approximate."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
