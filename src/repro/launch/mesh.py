"""Production mesh definitions.

Target: TPU v5e pods — 256 chips/pod in a (16, 16) ("data", "model") layout;
multi-pod adds a leading "pod" axis (2 pods = 512 chips) used for data
parallelism across pods (DCN-ish axis).  Built on demand — importing this
module never touches jax device state.

``make_data_mesh`` is the engine-facing entry point: a 1-D ("data",) mesh
over the host's devices, the axis the sharded hot loop
(:mod:`repro.engine.hotloop`) splits the instance batch over.  On a CPU
host, fake devices come from ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` — set *before* jax import (the sharded tests and
``benchmarks/engine_sweep.py --devices N`` both do).
"""

from __future__ import annotations

from typing import Optional

import jax


def _axis_kw(n_axes: int) -> dict:
    """Every mesh axis is an Auto (compiler-sharded) axis."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_host_mesh(model: int = 1, data: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    data = max(1, min(data, n // model))
    return jax.make_mesh((data, model), ("data", "model"), **_axis_kw(2))


def make_data_mesh(n_devices: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D ("data",) mesh for the engine's sharded hot loop.

    Uses ``n_devices`` devices (default: all available).  The engine shards
    its leading instance axis B over this axis — ``pack_instances(...,
    mesh=...)`` pads B to a multiple of the axis size with born-done dummy
    instances so every shard carries an equal slice.
    """
    avail = len(jax.devices())
    n = avail if n_devices is None else n_devices
    if not 1 <= n <= avail:
        raise ValueError(f"need 1 <= n_devices <= {avail}, got {n}")
    return jax.make_mesh((n,), ("data",), **_axis_kw(1))
