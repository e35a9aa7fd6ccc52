"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required for the smoke tests / benches that
must see exactly one CPU device.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    With 512 placeholder host devices (dry-run), the single-pod mesh takes
    the first 256 devices explicitly.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        # Auto axes: model code shards by GSPMD hints
        # (``distributed.constraints``), not by sharding-in-types
        return jax.make_mesh(shape, axes,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax")
    sub = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(sub, axes)


def make_mesh_from_devices(devices, shape, axes) -> jax.sharding.Mesh:
    """Elastic path: rebuild a (possibly smaller) mesh from surviving devices."""
    n = int(np.prod(shape))
    assert len(devices) >= n, (len(devices), shape)
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def data_axes(mesh: jax.sharding.Mesh):
    """Axes that shard the batch (and FSDP params): ('pod','data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: jax.sharding.Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None
