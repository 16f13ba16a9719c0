"""Checkpoint save/restore: roundtrip, async, latest-step, elastic restore."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.train.checkpoint import Checkpointer, latest_step, restore, save


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"layers": {"w": jax.random.normal(k, (8, 16), jnp.bfloat16),
                       "b": jnp.zeros((16,), jnp.float32)},
            "step_count": jnp.int32(7),
            "nested": [jnp.ones((3,)), {"m": jnp.arange(5)}]}


def test_roundtrip(tmp_path):
    t = _tree()
    save(tmp_path, 10, t)
    restored, step = restore(tmp_path, t)
    assert step == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_latest_step_and_gc(tmp_path):
    assert latest_step(tmp_path) is None
    ck = Checkpointer(tmp_path, every=2, keep=2)
    t = _tree()
    for s in range(1, 9):
        ck.maybe_save(s, t)
    ck.wait()
    assert latest_step(tmp_path) == 8
    steps = sorted(int(p.name.split("_")[1])
                   for p in tmp_path.glob("step_*"))
    assert len(steps) <= 2  # gc kept only the last `keep`


def test_restore_into_abstract(tmp_path):
    """Restore accepts ShapeDtypeStructs as the 'like' tree (fresh boot)."""
    t = _tree()
    save(tmp_path, 3, t)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    restored, step = restore(tmp_path, like)
    np.testing.assert_array_equal(
        np.asarray(restored["layers"]["w"], np.float32),
        np.asarray(t["layers"]["w"], np.float32))


def test_restore_shape_mismatch_raises(tmp_path):
    t = _tree()
    save(tmp_path, 1, t)
    bad = dict(t)
    bad["layers"] = {"w": jnp.zeros((9, 16), jnp.bfloat16),
                     "b": t["layers"]["b"]}
    with pytest.raises(ValueError):
        restore(tmp_path, bad)


def test_elastic_restore_resharding(tmp_path):
    """sharding_fn re-places leaves on the current (1-device) mesh —
    the elastic-restart path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    t = _tree()
    save(tmp_path, 5, t)
    mesh = make_mesh((1, 1), ("data", "model"))

    def sharding_fn(key, arr):
        return NamedSharding(mesh, P(*([None] * arr.ndim)))

    restored, _ = restore(tmp_path, t, sharding_fn=sharding_fn)
    w = restored["layers"]["w"]
    assert isinstance(w.sharding, NamedSharding)
    np.testing.assert_array_equal(np.asarray(w, np.float32),
                                  np.asarray(t["layers"]["w"], np.float32))
