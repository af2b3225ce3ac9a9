"""Checkpoint substrate: atomicity, retention, corruption detection,
async save, elastic restore."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": {"w": jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))},
            "b": jnp.arange(5, dtype=jnp.int32)}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 3, t, extra={"data": {"step": 3}})
    t2, extra, step = restore_checkpoint(str(tmp_path), t)
    assert step == 3 and extra["data"]["step"] == 3
    np.testing.assert_array_equal(np.asarray(t2["a"]["w"]),
                                  np.asarray(t["a"]["w"]))


def test_retention(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, t, keep=2)
    steps = sorted(int(d[5:]) for d in os.listdir(tmp_path))
    assert steps == [4, 5]


def test_atomic_no_tmp_left(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_corruption_detected(tmp_path):
    t = _tree()
    path = save_checkpoint(str(tmp_path), 1, t)
    # truncate one leaf file
    fn = [f for f in os.listdir(path) if f.endswith(".npy")][0]
    full = os.path.join(path, fn)
    arr = np.load(full)
    np.save(full, arr[:2])
    with pytest.raises((IOError, KeyError, ValueError)):
        restore_checkpoint(str(tmp_path), t)


def test_async_manager(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    t = _tree()
    mgr.save(1, t)
    mgr.save(2, t)  # waits for the first
    mgr.wait()
    assert mgr.latest_step() == 2


def test_elastic_restore_new_mesh(tmp_path):
    """Save unsharded, restore onto an explicit sharding (mesh of 1 here;
    the path exercises device_put-with-sharding, which is what a N->M
    chip restore uses)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    t = _tree()
    save_checkpoint(str(tmp_path), 7, t)
    sh = {"a": {"w": NamedSharding(mesh, P(None, None))},
          "b": NamedSharding(mesh, P())}
    t2, _, _ = restore_checkpoint(str(tmp_path), t, shardings=sh)
    assert t2["a"]["w"].sharding == sh["a"]["w"]
