"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e pods — 256 chips/pod in
a (16, 16) = (data, model) layout; the multi-pod mesh prepends a "pod" axis
(2 x 16 x 16 = 512 chips).  In STAR terms each pod holds a complete replica
of the parameters (the "full replica"); optimizer state is owner-sharded
("partial replicas") over the data axis inside each pod.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices):
    # Auto axes: the model code relies on compiler-propagated shardings
    # (GSPMD); jax.make_mesh's default Explicit axes would demand an
    # out_sharding on every gather over a sharded dim
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    return _make_mesh(shape, axes, devices)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests / examples)."""
    n = data * model
    devices = jax.devices()[:n]
    return _make_mesh((data, model), ("data", "model"), devices)
