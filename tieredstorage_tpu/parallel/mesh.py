"""Device mesh helpers for sharding chunk batches across chips.

`MeshPlan` is the production handle: built from the `transform.mesh.devices`
config (0/"all" = every local chip — the default for configured backends;
1 = single-chip, exactly the unsharded behavior; n = the first n local
devices), it owns row padding, placement, and the per-device accounting the
transform backend reports through `DispatchStats`. A plan whose mesh would
have a single device normalizes to the host-fallback plan (mesh ``None``),
so single-chip environments never pay the shard_map layer at all.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def data_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first `n_devices` devices (default: all)."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(f"Requested {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (DATA_AXIS,))


def shard_rows(mesh: Mesh, array) -> jax.Array:
    """Place an array with its leading (batch) axis sharded over the mesh.

    The batch must be divisible by the mesh size — callers pad with dummy
    rows (the transform backend does) before sharding. On a 1-device mesh
    this is an ordinary placement onto that device (no-op sharding).
    """
    spec = P(DATA_AXIS, *([None] * (array.ndim - 1)))
    return jax.device_put(array, NamedSharding(mesh, spec))


def pad_batch(n_rows: int, mesh: Optional[Mesh]) -> int:
    """Rows to add so the batch divides evenly across the mesh."""
    if mesh is None:
        return 0
    size = mesh.devices.size
    return (-n_rows) % size


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """How one transform window fans out over the local chips.

    ``mesh is None`` is the host-fallback/single-chip plan: plain
    ``device_put`` staging, no shard_map, no padding — byte-for-byte the
    pre-mesh behavior. A real mesh shards the packed window's row axis
    (``P(DATA_AXIS, None, ...)``) so ONE logical dispatch runs on every
    chip; input and output carry the identical row sharding, which is what
    lets the staged buffer stay donatable to XLA.
    """

    mesh: Optional[Mesh] = None

    @property
    def size(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    def pad_rows(self, n_rows: int) -> int:
        """Rows to add so the batch divides evenly across the mesh."""
        return pad_batch(n_rows, self.mesh)

    def rows_per_device(self, n_rows: int) -> int:
        """Per-chip row count for an (already padded) batch."""
        return (n_rows + self.pad_rows(n_rows)) // self.size

    def shard(self, array) -> jax.Array:
        """Stage a host array: row-sharded over the mesh, or a plain
        single-device placement on the fallback plan."""
        if self.mesh is None:
            return jax.device_put(array)
        return shard_rows(self.mesh, array)

    def describe(self) -> dict:
        """Mesh shape for reports/trajectory JSON ({} on the fallback plan)."""
        if self.mesh is None:
            return {}
        return {str(k): int(v) for k, v in self.mesh.shape.items()}

    @classmethod
    def wrap(cls, mesh: Union[None, Mesh, "MeshPlan"]) -> "MeshPlan":
        """Adopt a caller-supplied mesh (legacy `TpuTransformBackend(mesh=)`
        argument) or pass a plan through; a 1-device mesh normalizes to the
        fallback plan."""
        if isinstance(mesh, cls):
            plan = mesh
        else:
            plan = cls(mesh)
        if plan.mesh is not None and plan.mesh.devices.size <= 1:
            return cls(None)
        return plan

    @classmethod
    def from_spec(cls, spec: Union[None, int, str]) -> "MeshPlan":
        """Build the plan the `transform.mesh.devices` config asks for.

        ``None``/``0``/``"all"`` = every local device (the configured
        default — per-broker throughput scales with local chip count);
        ``1`` = single-chip (exactly the unsharded path); ``n`` = the
        first n local devices (raises when fewer are attached). Whenever
        the resulting mesh would hold one device the fallback plan is
        returned, so single-chip hosts never trace shard_map programs.
        """
        if isinstance(spec, str):
            text = spec.strip().lower()
            if text in ("", "all"):
                spec = None
            else:
                spec = int(text)
        if spec is not None and spec < 0:
            raise ValueError(f"transform.mesh.devices must be >= 0, got {spec}")
        n: Optional[int] = None if spec in (None, 0) else int(spec)
        if n == 1:
            return cls(None)
        mesh = data_mesh(n)
        return cls.wrap(mesh)
