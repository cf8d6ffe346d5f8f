"""Production mesh construction (functions only — importing this module never
touches jax device state)."""
from __future__ import annotations

import os
import re

import jax

BATCH_AXIS = "batch"


def forced_host_devices(count: int) -> None:
    """Force the CPU backend to expose ``count`` host devices.

    Idempotent XLA_FLAGS edit: replaces any existing
    ``--xla_force_host_platform_device_count`` value rather than appending a
    second one. Only effective if called before the CPU backend initializes
    (i.e. before the first jax array/device query in the process).
    """
    flag = f"--xla_force_host_platform_device_count={int(count)}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       flag, flags)
    else:
        flags = (flags + " " + flag).strip()
    os.environ["XLA_FLAGS"] = flags


def make_batch_mesh(num_devices: int | None = None):
    """1-D mesh over the engine's anonymous stacked batch axis (DESIGN.md
    §14). ``None`` takes every visible device. The axis is ``Auto``: the
    sessions shard it inside ``shard_map`` and hand back ordinary arrays,
    which callers index per entry (an ``Explicit`` axis, ``make_mesh``'s
    default, would make every such index a sharding-typed gather)."""
    n = jax.device_count() if num_devices is None else int(num_devices)
    if n > jax.device_count():
        raise ValueError(
            f"requested a {n}-device batch mesh but only "
            f"{jax.device_count()} device(s) are visible — on CPU, call "
            "repro.launch.mesh.forced_host_devices before jax initializes")
    return jax.make_mesh((n,), (BATCH_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (batch/fsdp) × ``model`` (tensor/expert). The multi-pod
    mesh adds a leading ``pod`` axis — in the VFL mapping each pod is one
    party (DESIGN.md §3), and only the one-shot protocol's rep/grad
    exchanges cross it.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(devices_per_axis=(2, 2)):
    """Small host mesh for CI-sized sharding tests."""
    axes = ("data", "model") if len(devices_per_axis) == 2 else ("pod", "data", "model")
    return jax.make_mesh(tuple(devices_per_axis), axes)
