"""Device-parallel execution of the stacked engine axis (DESIGN.md §14).

Every fold so far — K parties (§2), S seeds (§10–11), C scenarios (§12) —
stacks entries on one ANONYMOUS leading batch axis and runs them as a
single vmapped program on ONE device. This module adds the last axis: a
1-D device mesh over which that stacked axis shards via ``shard_map``,
so an S·C·K-entry program runs W/D entries per device with near-linear
scaling and unchanged per-entry math.

Design rules (mirroring every previous fold):

* **The single-device path is the no-mesh case.** ``resolve_mesh``
  normalizes ``None`` / ``1`` / a 1-device mesh to ``None``; the cache-key
  component :func:`mesh_key` is then ``None`` and the compiled sessions are
  byte-for-byte the historical single-device programs.
* **Cache keys gain mesh identity, never width.** Session-cache keys
  extend with ``(axis_names, mesh_shape)`` — NOT the stacked batch width —
  so a warm cache at one batch width serves every other width on the same
  mesh (``jax.jit`` re-specializes per shape), and the first sharded run
  against a warm single-device cache takes exactly one mesh-keyed miss per
  session kind.
* **Pad host-side, strip host-side.** ``shard_map`` needs the leading axis
  divisible by the device count; :func:`pad_entries` / :func:`pad_stacked`
  append copies of entry 0 (real work whose outputs are discarded — entries
  are independent by construction, so dummies cannot perturb real ones) and
  the callers slice the first W results back out. Communication ledgers are
  logged host-side from the *real* entries only, so they stay byte-identical
  to the single-device fold.
* **Steering.** The mesh arrives via ``ProtocolConfig.mesh`` /
  ``IterativeConfig.mesh`` (``None`` | device count | ``jax.sharding.Mesh``)
  or the env knob ``REPRO_DEVICE_COUNT``. Results record
  ``diagnostics["device_fold"]`` alongside ``seed_fold`` /
  ``scenario_fold``.
"""
from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from repro.launch.mesh import BATCH_AXIS, make_batch_mesh


def resolve_mesh(mesh: Any = None) -> Optional[Mesh]:
    """Normalize a mesh request to ``Mesh`` or ``None`` (single-device).

    Accepts ``None`` (consult ``REPRO_DEVICE_COUNT``, else single-device),
    an ``int`` device count, or a ``jax.sharding.Mesh``. A width-1 request
    normalizes to ``None`` so the single-device path is literally the
    1-device mesh case under the same cache-key discipline. Idempotent —
    safe to call at every layer the mesh threads through.
    """
    if mesh is None:
        env = os.environ.get("REPRO_DEVICE_COUNT", "")
        if not env:
            return None
        mesh = int(env)
    if isinstance(mesh, int):
        if mesh <= 1:
            return None
        mesh = make_batch_mesh(mesh)
    if mesh.size <= 1:
        return None
    return mesh


def device_fold(mesh: Optional[Mesh]) -> int:
    """The device-axis fold width a resolved mesh implies (1 = no mesh)."""
    return 1 if mesh is None else int(mesh.size)


def mesh_key(mesh: Optional[Mesh]):
    """Hashable mesh identity for session-cache keys: axis names + shape,
    never the stacked batch width. ``None`` on the single-device path, so
    the historical single-device cache keys are unchanged."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape))


def pad_width(n: int, mesh: Optional[Mesh]) -> int:
    """Entries to append so ``n`` divides the mesh's device count."""
    return 0 if mesh is None else (-n) % mesh.size


def pad_entries(entries: Sequence[Any], mesh: Optional[Mesh]) -> List[Any]:
    """Pad a flat host-side entry list to a device-count multiple by
    repeating entry 0; callers strip results back to ``len(entries)``."""
    entries = list(entries)
    return entries + [entries[0]] * pad_width(len(entries), mesh)


def pad_stacked(tree: Any, pad: int) -> Any:
    """Append ``pad`` copies of entry 0 along axis 0 of every leaf of an
    already-stacked pytree (the device-divisibility padding for arguments
    that arrive stacked rather than as host lists)."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate([a, jnp.repeat(a[:1], pad, axis=0)],
                                  axis=0), tree)


def strip_stacked(tree: Any, n: int) -> Any:
    """Inverse of :func:`pad_stacked`: keep the first ``n`` entries."""
    return jax.tree_util.tree_map(lambda a: a[:n], tree)


def shard_jit(fn, mesh: Optional[Mesh], donate_params: bool = True,
              compiler_options: Optional[dict] = None):
    """Compile a batched session over the stacked leading axis.

    ``mesh is None`` → the historical single-device ``jax.jit`` (stacked
    params donated). Otherwise the session is wrapped in ``shard_map`` with
    every input/output leaf sharded on its leading axis over ``BATCH_AXIS``
    — entries are independent, so per-device execution of W/D-entry slices
    is exactly the single-device program restricted to each slice. Donation
    is disabled on the sharded path: inputs arrive host-committed and are
    resharded onto the mesh, so their buffers are not reusable in place.
    ``compiler_options`` pass through to ``jax.jit`` unchanged.
    """
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0,) if donate_params else (),
                       compiler_options=compiler_options)
    spec = PartitionSpec(BATCH_AXIS)
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False),
                   compiler_options=compiler_options)
