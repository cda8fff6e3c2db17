"""Checkpoint store tests: round trip, torn saves, shape checks, retention
and restoring onto a sharding."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.launch.mesh import make_mesh


# --- checkpoint ---------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(12.0).reshape(3, 4), "s": jnp.int32(7)}
    save_checkpoint(tmp_path, 3, tree, {"step": 3})
    assert latest_step(tmp_path) == 3
    restored, extra = restore_checkpoint(tmp_path, None, tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert extra["step"] == 3


def test_checkpoint_ignores_uncommitted(tmp_path):
    save_checkpoint(tmp_path, 1, {"w": jnp.ones(2)})
    # fake a torn save at step 2
    (tmp_path / "step_00000002").mkdir()
    assert latest_step(tmp_path) == 1


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(tmp_path, 0, {"w": jnp.ones((2, 2))})
    with pytest.raises(ValueError):
        restore_checkpoint(tmp_path, 0, {"w": jnp.ones((3, 3))})


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, {"w": jnp.full((2,), float(s))})
    mgr.wait()
    assert latest_step(tmp_path) == 4
    kept = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("step_"))
    assert len(kept) == 2


def test_checkpoint_elastic_resharding(tmp_path):
    """Save unsharded, restore with an explicit sharding on the current mesh."""
    mesh = make_mesh((len(jax.devices()),), ("data",))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(tmp_path, 0, tree)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = restore_checkpoint(tmp_path, 0, tree, sh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert restored["w"].sharding == sh["w"]
