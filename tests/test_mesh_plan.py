"""parallel/mesh.py edge cases: padding math, degenerate meshes, MeshPlan
spec parsing, and `jax.shard_map` as the window programs spell it."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from tieredstorage_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    MeshPlan,
    data_mesh,
    pad_batch,
    shard_rows,
)


class TestPadBatch:
    @pytest.mark.parametrize(
        "rows,devices,expected",
        [(11, 8, 5), (16, 8, 0), (1, 8, 7), (8, 8, 0), (3, 4, 1), (9, 2, 1)],
    )
    def test_non_divisible_batches(self, rows, devices, expected):
        assert pad_batch(rows, data_mesh(devices)) == expected

    def test_no_mesh_no_padding(self):
        assert pad_batch(11, None) == 0

    def test_plan_pad_and_rows_per_device(self):
        plan = MeshPlan.from_spec(8)
        assert plan.pad_rows(11) == 5
        assert plan.rows_per_device(11) == 2
        assert MeshPlan(None).pad_rows(11) == 0
        assert MeshPlan(None).rows_per_device(11) == 11


class TestDegenerateMeshes:
    def test_shard_rows_on_one_device_mesh_is_noop_placement(self):
        mesh = data_mesh(1)
        arr = np.arange(24, dtype=np.uint8).reshape(6, 4)
        placed = shard_rows(mesh, arr)
        # Everything lives on the mesh's single device, bytes unchanged.
        assert placed.sharding.is_fully_replicated or len(placed.devices()) == 1
        assert {d for d in placed.devices()} == {mesh.devices.item(0)}
        np.testing.assert_array_equal(np.asarray(placed), arr)

    def test_data_mesh_rejects_more_than_available(self):
        available = len(jax.devices())
        with pytest.raises(ValueError, match="Requested"):
            data_mesh(available + 1)

    def test_shard_rows_distributes_rows(self):
        mesh = data_mesh(8)
        arr = np.arange(8 * 4, dtype=np.uint8).reshape(8, 4)
        placed = shard_rows(mesh, arr)
        assert len(placed.devices()) == 8
        np.testing.assert_array_equal(np.asarray(placed), arr)


class TestMeshPlanSpec:
    @pytest.mark.parametrize("spec", [None, 0, "0", "all", "ALL", ""])
    def test_all_local_devices(self, spec):
        plan = MeshPlan.from_spec(spec)
        assert plan.size == len(jax.devices())
        assert plan.describe() == {DATA_AXIS: plan.size}

    @pytest.mark.parametrize("spec", [1, "1"])
    def test_one_means_the_unsharded_fallback_plan(self, spec):
        plan = MeshPlan.from_spec(spec)
        assert plan.mesh is None and plan.size == 1
        assert plan.describe() == {}

    def test_explicit_count(self):
        plan = MeshPlan.from_spec(4)
        assert plan.size == 4

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="Requested"):
            MeshPlan.from_spec(len(jax.devices()) + 1)

    def test_negative_rejected(self):
        with pytest.raises(ValueError, match=">= 0"):
            MeshPlan.from_spec(-2)

    def test_wrap_normalizes_single_device_mesh(self):
        assert MeshPlan.wrap(data_mesh(1)).mesh is None
        assert MeshPlan.wrap(None).mesh is None
        plan = MeshPlan.from_spec(4)
        assert MeshPlan.wrap(plan) is plan
        assert MeshPlan.wrap(data_mesh(2)).size == 2

    def test_fallback_plan_shard_places_on_default_device(self):
        arr = np.arange(12, dtype=np.uint8).reshape(3, 4)
        placed = MeshPlan(None).shard(arr)
        np.testing.assert_array_equal(np.asarray(placed), arr)


class TestShardMapOnTheMesh:
    def test_real_shard_map_runs_on_the_mesh(self):
        """`jax.shard_map(..., check_vma=False)`, as the window programs
        spell it, end to end on the virtual mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = data_mesh(8)
        data = np.arange(16, dtype=np.int32).reshape(8, 2)
        fn = jax.jit(
            jax.shard_map(
                lambda x: x * 2, mesh=mesh,
                in_specs=(P(DATA_AXIS, None),), out_specs=P(DATA_AXIS, None),
                check_vma=False,
            )
        )
        out = fn(jax.device_put(data, NamedSharding(mesh, P(DATA_AXIS, None))))
        np.testing.assert_array_equal(np.asarray(out), data * 2)
