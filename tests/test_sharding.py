"""Logical-axis sharding rules: divisibility fallbacks, axis-reuse
prevention, spec building — pure-host tests (AbstractMesh, no devices)."""

import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.sharding import BASELINE, GRIDLOCAL, ShapeAxes, logical_to_pspec

MESH1 = AbstractMesh((16, 16), ("data", "model"))
MESH2 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestLogicalToPspec:
    def test_basic_tp(self):
        sp = logical_to_pspec(("embed", "mlp"), (4096, 16384), BASELINE, MESH1)
        assert sp == P("data", "model")

    def test_batch_uses_pod_and_data(self):
        sp = logical_to_pspec(("batch", "seq"), (256, 4096), BASELINE, MESH2)
        assert sp == P(("pod", "data"))

    def test_batch_single_pod_mesh_drops_pod(self):
        sp = logical_to_pspec(("batch", "seq"), (256, 4096), BASELINE, MESH1)
        assert sp == P("data")

    def test_indivisible_dim_falls_back_to_replicated(self):
        # 8 experts cannot shard over model=16
        sp = logical_to_pspec(("experts", "embed", "expert_mlp"), (8, 6144, 16384), BASELINE, MESH1)
        assert sp == P(None, "data", "model")
        # 64 experts CAN
        sp2 = logical_to_pspec(("experts", "embed", "expert_mlp"), (64, 2048, 1408), BASELINE, MESH1)
        assert sp2[0] == "model"

    def test_axis_never_reused_across_dims(self):
        # batch takes data; kv_seq would also want data -> dropped
        sp = logical_to_pspec(
            ("batch", "kv_seq", "kv_heads", None), (128, 32768, 4, 256), BASELINE, MESH1
        )
        assert sp == P("data")  # trailing Nones trimmed; no double 'data'

    def test_batch1_long_context_gives_data_to_cache(self):
        sp = logical_to_pspec(
            ("batch", "kv_seq", "kv_heads", None), (1, 524288, 4, 256), BASELINE, MESH1
        )
        assert sp[0] is None
        assert sp[1] == "data"

    def test_partial_divisibility_prefix(self):
        # dim 32 with rule (pod, data) = 2*16: full product divides
        sp = logical_to_pspec(("batch",), (32,), BASELINE, MESH2)
        assert sp == P(("pod", "data"))
        # dim 2 only allows pod (singleton tuples canonicalize to the bare
        # axis name on current jax; older versions keep them distinct)
        sp2 = logical_to_pspec(("batch",), (2,), BASELINE, MESH2)
        assert sp2 in (P("pod"), P(("pod",)))


class TestShapeAxes:
    def test_struct_with_and_without_mesh(self):
        sa = ShapeAxes(shape=(64, 128), dtype="float32", axes=("embed", "mlp"))
        s0 = sa.struct()
        assert s0.shape == (64, 128) and s0.sharding is None

    def test_default_axes_fill(self):
        sa = ShapeAxes(shape=(3, 4, 5), dtype="int32")
        assert sa.axes == (None, None, None)

    def test_axes_length_checked(self):
        with pytest.raises(AssertionError):
            ShapeAxes(shape=(3, 4), dtype="f4", axes=("a",))


class TestGridlocalRules:
    def test_grid_axis_maps_to_pod(self):
        sp = logical_to_pspec(("grid", "vocab", "embed"), (2, 32000, 4096), GRIDLOCAL, MESH2)
        assert sp[0] == "pod"

    def test_gridlocal_batch_excludes_pod(self):
        sp = logical_to_pspec(("batch", "seq"), (256, 4096), GRIDLOCAL, MESH2)
        assert sp == P("data")
