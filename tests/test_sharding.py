"""logical_to_spec divisibility guard + rule behaviour (no fake devices:
uses a (1,1) mesh for plumbing and pure-function checks for the guard),
plus the op-level shard_assignment/local_shapes contract the sharded
kernel dispatch plans against."""

import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.api import (AxisSpec, local_shapes, logical_to_spec,
                                set_mesh, shard, shard_assignment,
                                current_mesh)


class _FakeMesh:
    """Duck-typed mesh exposing .shape for guard tests."""
    def __init__(self, shape):
        self.shape = shape


def _spec(shape, logical, mesh_shape):
    return logical_to_spec(shape, logical, _FakeMesh(mesh_shape))


def test_divisible_dims_shard():
    assert _spec((64000, 7168), ("vocab", "fsdp"),
                 {"data": 16, "model": 16}) == P("model", "data")


def test_indivisible_dims_drop():
    # 51865 % 16 != 0 -> vocab axis dropped
    assert _spec((51865, 512), ("vocab", "fsdp"),
                 {"data": 16, "model": 16}) == P(None, "data")


def test_axis_used_once():
    # batch takes pod+data; fsdp (data) already consumed -> dropped
    assert _spec((256, 4096, 16), ("batch", "seq", "fsdp"),
                 {"pod": 2, "data": 16, "model": 16}) \
        == P(("pod", "data"), "model", None)


def test_batch_multi_axis_partial():
    # batch 8 on (pod=2, data=16): pod divides, pod*data doesn't -> pod only
    assert _spec((8, 10), ("batch", None), {"pod": 2, "data": 16}) \
        == P("pod", None)


def test_kv_seq_uses_model_then_data():
    # long_500k: batch 1 -> both axes free for the sequence
    assert _spec((1, 524288, 8, 128), ("batch", "kv_seq", None, None),
                 {"data": 16, "model": 16}) == P(None, ("model", "data"), None,
                                                 None)


def test_missing_axis_ignored():
    assert _spec((128, 128), ("batch", None), {"model": 4}) == P(None, None)


def test_no_mesh_is_noop():
    import jax.numpy as jnp
    assert current_mesh() is None
    x = jnp.ones((4, 4))
    y = shard(x, "batch", None)  # must not raise without a mesh
    assert (y == x).all()


def test_set_mesh_plumbing():
    mesh = make_mesh((1, 1), ("data", "model"))
    with set_mesh(mesh):
        assert current_mesh() is mesh
        spec = logical_to_spec((16, 16), ("fsdp", "tp"))
        assert spec == P("data", "model")
    assert current_mesh() is None


def test_custom_rules():
    rules = AxisSpec((("batch", ("x",)),))
    assert logical_to_spec((8,), ("batch",), _FakeMesh({"x": 4}),
                           rules) == P("x")
    assert logical_to_spec((8,), ("unknown",), _FakeMesh({"x": 4}),
                           rules) == P(None)


def test_rank_mismatch_raises_descriptive_valueerror():
    """Shape/logical rank disagreement names both, with or without a
    mesh (the guard is not mesh-gated)."""
    with pytest.raises(ValueError) as err:
        logical_to_spec((4, 8), ("batch",), _FakeMesh({"data": 2}))
    msg = str(err.value)
    assert "(4, 8)" in msg and "('batch',)" in msg
    with pytest.raises(ValueError, match="same rank"):
        logical_to_spec((4, 8), ("batch",))        # no mesh: still raises


# ---------------------------------------------------------------------------
# shard_assignment / local_shapes: the op-level contract the sharded
# kernel dispatch plans against
# ---------------------------------------------------------------------------

_MESH = _FakeMesh({"data": 2, "model": 4})
_ATTN = {"B": 4, "S": 128, "T": 128, "H": 8, "KV": 4, "hd": 32}
_ATTN_LOGICAL = {"B": "batch", "H": "heads", "KV": "heads"}


def test_grouped_dims_co_shard():
    """Q heads and KV heads share "heads": both shard by the same factor,
    so the kernel's H/KV ratio (GQA group size) survives partitioning."""
    asn = shard_assignment(_ATTN, _ATTN_LOGICAL, _MESH)
    assert asn.counts["H"] == 4 and asn.counts["KV"] == 4
    assert asn.counts["B"] == 2
    assert asn.axes_of["H"] == ("model",) == asn.axes_of["KV"]
    assert local_shapes(_ATTN, _ATTN_LOGICAL, _MESH) == {
        "B": 2, "S": 128, "T": 128, "H": 2, "KV": 1, "hd": 32}


def test_group_member_indivisible_blocks_the_axis():
    """KV=2 cannot take the 4-way model axis, so H must not either —
    sharding H alone would break the grouped ratio."""
    shapes = dict(_ATTN, KV=2)
    asn = shard_assignment(shapes, _ATTN_LOGICAL, _MESH)
    assert asn.counts["H"] == 1 and asn.counts["KV"] == 1
    assert "H" not in asn.axes_of


def test_size_one_group_member_broadcasts():
    """Mamba-2's single B/C group (or MQA's single KV head) never blocks
    head sharding: size-1 dims replicate and every local head still maps
    to group 0."""
    ssd = {"B": 4, "S": 64, "nh": 8, "hd": 16, "ds": 16, "G": 1}
    logical = {"B": "batch", "nh": "heads", "G": "heads"}
    asn = shard_assignment(ssd, logical, _MESH)
    assert asn.counts["nh"] == 4 and asn.counts["G"] == 1
    assert asn.spec("B", None, "G", None) == P("data", None, None, None)


def test_assignment_axis_used_once():
    """A mesh axis feeds at most one logical axis (first-appearance
    order), mirroring logical_to_spec."""
    shapes = {"E": 8, "H": 8}
    asn = shard_assignment(shapes, {"E": "expert", "H": "heads"}, _MESH)
    assert asn.counts["E"] == 4 and asn.counts["H"] == 1


def test_assignment_spec_matches_counts():
    asn = shard_assignment(_ATTN, _ATTN_LOGICAL, _MESH)
    assert asn.spec("B", None, "H", None) == P("data", None, "model", None)
    assert asn.spec("B", None, "KV", None) == P("data", None, "model", None)
    assert asn.spec("B") == P("data")


def test_local_shapes_without_mesh_is_identity():
    assert current_mesh() is None
    assert local_shapes(_ATTN, _ATTN_LOGICAL) == _ATTN


def test_assignment_unknown_dim_raises():
    with pytest.raises(ValueError, match="names dims"):
        shard_assignment({"B": 4}, {"B": "batch", "G": "heads"}, _MESH)
