"""Subprocess body: sharded hdiff == single-device hdiff on 8 fake devices.

Run by tests/test_dist.py with XLA_FLAGS=--xla_force_host_platform_device_count=8.
Exits nonzero (assertion) on any mismatch.
"""

import os

assert "--xla_force_host_platform_device_count=8" in os.environ.get("XLA_FLAGS", "")

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hdiff, hdiff_simple
from repro.dist import make_sharded_hdiff
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 8

rng = np.random.default_rng(0)
psi = jnp.asarray(rng.standard_normal((8, 32, 16)).astype(np.float32))
want = np.asarray(hdiff(psi, 0.025))

# --- depth-parallel over all 8 devices (paper's plane-per-B-block) ----------
mesh = make_mesh((8, 1), ("data", "model"))
fn = make_sharded_hdiff(mesh, depth_axis="data", row_axis=None)
got = np.asarray(fn(psi))
np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
print("depth-parallel ok")

# --- row decomposition with halo exchange (4-way) ----------------------------
mesh = make_mesh((2, 4), ("data", "model"))
fn = make_sharded_hdiff(mesh, depth_axis="data", row_axis="model")
got = np.asarray(fn(psi))
np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
print("row-halo ok")

# --- row decomposition, 8-way, rows barely larger than halo ------------------
mesh = make_mesh((1, 8), ("data", "model"))
fn = make_sharded_hdiff(mesh, depth_axis=None, row_axis="model")
got = np.asarray(fn(psi))
np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
print("row-halo-8 ok")

# --- simple (unlimited) variant ----------------------------------------------
want_s = np.asarray(hdiff_simple(psi, 0.025))
mesh = make_mesh((2, 4), ("data", "model"))
fn = make_sharded_hdiff(mesh, depth_axis="data", row_axis="model", limit=False)
np.testing.assert_allclose(np.asarray(fn(psi)), want_s, rtol=1e-6, atol=1e-6)
print("simple ok")

print("ALL_OK")
