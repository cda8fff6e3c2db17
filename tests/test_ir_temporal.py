"""Temporal blocking: repeat/compose analysis + k-step boundary semantics.

Per-backend k-step parity cells (k in {1, 2, 3} x every backend x every
mesh) live in the conformance matrix (tests/conformance.py); this file
keeps the graph-level composition invariants, the boundary-ring semantics
that distinguish stepped from pure-DAG execution, the 1-D chain path, and
the paper-grid acceptance run.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import hdiff
from repro.core.stencils import jacobi1d
from repro.ir import (
    StencilProgram,
    affine,
    hdiff_program,
    jacobi1d_program,
    jacobi2d_5pt_program,
    laplacian_program,
    lower_pallas,
    lower_reference,
    lower_sharded,
    repeat,
)
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(23)


def _grid(*shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


def _composed(fn, x, k):
    for _ in range(k):
        x = fn(x)
    return np.asarray(x)


# --- graph-level composition -------------------------------------------------


def test_repeat_radius_and_steps_scale():
    p = hdiff_program()
    for k in (1, 2, 3, 5):
        pk = repeat(p, k)
        assert pk.radius == k * p.radius
        assert pk.steps == k
        assert len(pk.chain) == k
        assert all(c is p for c in pk.chain)
    assert repeat(p, 1) is p


def test_compose_chains_heterogeneous_programs():
    a, b = laplacian_program(), jacobi2d_5pt_program()
    ab = a.compose(b)
    assert ab.radius == a.radius + b.radius == 2
    assert ab.steps == 2
    assert ab.chain == (a, b)
    # Deeper stacking keeps field names unique and radii additive.
    abab = ab.compose(ab)
    assert abab.radius == 4 and abab.steps == 4
    names = [op.name for op in abab.ops]
    assert len(names) == len(set(names))


def test_compose_validation():
    p = hdiff_program()
    two_in = StencilProgram(
        "two", ["a", "b"], [affine("out", "a", {(0, 0): 1.0})]
    )
    # hdiff has no "b" input to share, so feeding two_in after it fails.
    with pytest.raises(ValueError, match="shared field"):
        p.compose(two_in)
    with pytest.raises(ValueError, match="ndim"):
        p.compose(jacobi1d_program())
    with pytest.raises(ValueError, match="positive int"):
        repeat(p, 0)
    # Multi-field self-composition is legal: the passthrough input evolves,
    # the shared field feeds both sweeps.
    two_k = repeat(two_in, 2)
    assert two_k.steps == 2 and two_k.inputs == ("a", "b")
    assert two_k.field_radii() == {"a": 0, "b": 0}


def test_repeat_per_step_accounting_divides_by_k():
    p = hdiff_program()
    points = 64 * 256 * 256
    for k in (1, 2, 4):
        pk = repeat(p, k)
        # One fused residency still moves (inputs + output) once...
        assert pk.fused_bytes(points) == p.fused_bytes(points)
        # ...so per-simulated-step traffic divides by k.
        assert pk.fused_bytes_per_step(points) == p.fused_bytes(points) / k


# --- k-step 1-D chain path (outside the 2-D conformance matrix) ---------------


@pytest.mark.parametrize("k", [2, 3])
def test_kstep_jacobi1d_matches_composed(k):
    x1 = _grid(3, 24)
    want1 = _composed(jacobi1d, x1, k)
    got1 = np.asarray(lower_pallas(repeat(jacobi1d_program(), k), interpret=True)(x1))
    np.testing.assert_allclose(got1, want1, rtol=1e-6, atol=1e-6)


def test_kstep_block_rows_down_to_chain_halo():
    """Whole neighbour tiles only need block_rows >= k*r (one neighbour
    block sources the whole band); the smallest legal tile must agree."""
    x = _grid(1, 16, 12)
    want = _composed(lambda a: hdiff(a, 0.025), x, 2)
    pk = repeat(hdiff_program(), 2)  # chain halo 4
    got = np.asarray(lower_pallas(pk, block_rows=4, interpret=True)(x))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="inferred row halo"):
        lower_pallas(pk, block_rows=2, interpret=True)(x)


def test_kstep_boundary_ring_passthrough_per_sweep():
    """The OUTER radius-r ring holds the input after every sweep; rows in
    [r, k*r) are computed (from ring passthrough values), NOT passed
    through — the distinction between stepped and pure-DAG semantics."""
    x = _grid(1, 20, 20)
    k = 2
    got = np.asarray(lower_pallas(repeat(hdiff_program(), k), interpret=True)(x))
    want = _composed(lambda a: hdiff(a, 0.025), x, k)
    np.testing.assert_array_equal(got[:, :2, :], np.asarray(x[:, :2, :]))
    np.testing.assert_array_equal(got[:, -2:, :], np.asarray(x[:, -2:, :]))
    # Rows 2..3 differ from the input (they are computed at sweep 2).
    assert np.abs(got[:, 2:4, 2:-2] - np.asarray(x[:, 2:4, 2:-2])).max() > 0
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_kstep_paper_grid_acceptance():
    """k in {1,2,3} on the paper's 64x256x256 domain, reference + Pallas."""
    x = _grid(64, 256, 256)
    want = np.asarray(x)
    for k in (1, 2, 3):
        want = np.asarray(hdiff(jnp.asarray(want), 0.025))
        pk = repeat(hdiff_program(), k)
        got_ref = np.asarray(lower_reference(pk)(x))
        np.testing.assert_allclose(got_ref, want, rtol=1e-6, atol=1e-6)
        got_pl = np.asarray(lower_pallas(pk, interpret=True)(x))
        np.testing.assert_allclose(got_pl, want, rtol=1e-6, atol=1e-6)


# --- k-step sharded lowering (1-device mesh; 8-device in tests/multidev) -----


def test_kstep_sharded_uses_chain_halo_in_validation():
    """The rows/shard floor is the CHAIN radius k*r: the k-step exchange
    needs the full band from the immediate neighbour."""
    mesh = make_mesh((1, 1), ("data", "model"))
    fn = lower_sharded(repeat(hdiff_program(), 3), mesh, row_axis="model")
    # 1 row shard: no exchange, any row count works.
    x = _grid(1, 16, 16)
    want = _composed(lambda a: hdiff(a, 0.025), x, 3)
    np.testing.assert_allclose(np.asarray(fn(x)), want, rtol=1e-6, atol=1e-6)
