"""Fault-tolerance behaviours of the checkpoint store: corrupted-checkpoint
detection at load and the health snapshot a save records."""

import json

import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint


# --- corrupted-checkpoint detection ---------------------------------------


def _save_small(root):
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones(4, dtype=np.float32)}
    save_checkpoint(root, 5, tree, {"note": "t"})
    return tree


def _restore(root, tree, **kwargs):
    like = {k: np.zeros_like(v) for k, v in tree.items()}
    return restore_checkpoint(root, 5, like, **kwargs)


def test_restore_rejects_wrong_leaf_shape(tmp_path):
    """A payload whose arrays no longer match what meta.json recorded must
    fail AT LOAD with a ValueError naming the leaf, not deep in re-shard."""
    tree = _save_small(tmp_path)
    d = tmp_path / "step_00000005"
    with np.load(d / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    arrays["a0"] = arrays["a0"][:1]  # truncate one leaf: bit rot / partial write
    np.savez(d / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match=r"leaf a0.*corrupt"):
        _restore(tmp_path, tree)


def test_restore_rejects_wrong_leaf_dtype(tmp_path):
    tree = _save_small(tmp_path)
    d = tmp_path / "step_00000005"
    with np.load(d / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    arrays["a1"] = arrays["a1"].astype(np.float64)
    np.savez(d / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match=r"leaf a1.*dtype"):
        _restore(tmp_path, tree)


def test_restore_rejects_missing_leaf(tmp_path):
    tree = _save_small(tmp_path)
    d = tmp_path / "step_00000005"
    with np.load(d / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files if k != "a1"}
    np.savez(d / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match=r"missing leaves \['a1'\]"):
        _restore(tmp_path, tree)


def test_restore_verifies_health_snapshot(tmp_path):
    """Same shapes/dtypes but different BYTES: the meta.json numerics-health
    snapshot (NaN/Inf counts + global L2) is recomputed at restore and a
    mismatch fails — silent value corruption can't ride a valid schema."""
    tree = _save_small(tmp_path)
    d = tmp_path / "step_00000005"
    with np.load(d / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    arrays["a1"] = arrays["a1"] * 2.0  # values changed, schema intact
    np.savez(d / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match="health snapshot mismatch"):
        _restore(tmp_path, tree)
    # Opt-out path still loads (the caller accepted the risk)...
    state, extra = _restore(tmp_path, tree, verify_health=False)
    assert extra == {"note": "t"}
    # ...and a NaN smuggled into the payload trips the count check too.
    arrays["a1"] = np.ones((2, 3), dtype=np.float32)
    arrays["a1"][0, 0] = np.nan
    np.savez(d / "arrays.npz", **arrays)
    with pytest.raises(ValueError, match="health snapshot mismatch"):
        _restore(tmp_path, tree)


def test_save_records_health_snapshot_and_clean_restore_passes(tmp_path):
    tree = _save_small(tmp_path)
    meta = json.loads((tmp_path / "step_00000005" / "meta.json").read_text())
    h = meta["health"]
    assert h["n_elements"] == 10 and h["nan_count"] == 0 and h["inf_count"] == 0
    want_l2 = float(np.sqrt(sum((v.astype(np.float64) ** 2).sum() for v in tree.values())))
    assert np.isclose(h["l2"], want_l2, rtol=1e-12)
    state, _ = _restore(tmp_path, tree)  # verify_health=True is the default
    np.testing.assert_array_equal(np.asarray(state["w"]), tree["w"])


def test_checkpoint_roundtrip_complex_leaves(tmp_path):
    """Complex leaves go through the health snapshot as |z|^2 (np.square
    with a float64 dtype arg rejects complex input) — save, L2, and the
    verify-on-restore path must all work."""
    c = (np.arange(6) + 1j * np.arange(6, 0, -1)).astype(np.complex64).reshape(2, 3)
    c[0, 0] = np.nan + 0j
    tree = {"c": c, "w": np.ones(3, dtype=np.float32)}
    save_checkpoint(tmp_path, 5, tree)
    meta = json.loads((tmp_path / "step_00000005" / "meta.json").read_text())
    h = meta["health"]
    assert h["nan_count"] == 1
    finite = c[np.isfinite(c)]
    want_l2 = float(np.sqrt((np.abs(finite).astype(np.float64) ** 2).sum() + 3.0))
    assert np.isclose(h["l2"], want_l2, rtol=1e-12)
    state, _ = _restore(tmp_path, tree)  # health verification on
    np.testing.assert_array_equal(np.asarray(state["c"]), c)
