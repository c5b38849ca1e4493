"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis composes
with 'data' for gradient reduction (crosses DCN once per step) and with
FSDP sharding; 'model' (TP/SP/EP) stays inside the ICI domain.

A FUNCTION, not a module-level constant: importing this module must not
touch jax device state (smoke tests run on 1 CPU device).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under dryrun.py (sets xla_force_host_platform_device_count)"
        )
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices[:n],
    )


def make_mesh_for(n_devices: Optional[int] = None, *,
                  model_axis: int = 1):
    """Small-scale mesh for tests/examples on whatever devices exist."""
    devices = jax.devices()
    n = n_devices or len(devices)
    data = n // model_axis
    return jax.make_mesh(
        (data, model_axis), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=devices[:n],
    )


def make_fleet_mesh(n_devices: Optional[int] = None, *,
                    axis: str = "replica"):
    """1-D mesh over the available devices for device-sharded fleet sweeps
    (``core.fleet.run_fleet(..., mesh=...)``) and shard_map PPO
    (``rl.distributed``): the replica/env axis partitions across ``axis``
    and everything else replicates."""
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise RuntimeError(
            f"need {n} devices for a fleet mesh, have {len(devices)} — "
            "force host devices via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return jax.make_mesh((n,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices[:n])
