"""Cross-backend conformance matrix: the single source of parity truth.

One place defines the grid, tolerance, seed, program set, backend set, k
(temporal-blocking) set and mesh set; every parity test — tier-1 single
device and the 8-fake-device multidev jobs — draws its cells from here
instead of re-declaring its own grid/tolerance (what test_ir_lowering.py,
test_ir_temporal.py and tests/multidev/_ir_check.py each used to do).

The oracle for every cell is ``lower_reference`` of the k-step composed
program; the oracle itself is anchored against the hand-written kernels by
``test_conformance_matrix.py::test_oracle_matches_handwritten``.

Cells:
  program  in {hdiff, hdiff_simple} + the five elementary 2-D stencils
           + the two multi-field workloads {vadvc, hdiff_coupled}
           + the two multi-OUTPUT coupled systems {shallow_water,
             advection_diffusion} (results compared per output field)
  backend  in {reference, staged, pallas, sharded-reference, sharded-pallas}
  k        in {1, 2, 3}
  mesh     in {1x1, 8x1, 2x4, 1x8}   (rows x cols shards; non-sharded
                                      backends are mesh-independent and run
                                      at 1x1 only)

GRID is sized so every cell is feasible: 48 rows / 8 shards = 6 rows per
shard == the deepest chain halo in the matrix (hdiff / hdiff_coupled
radius 2, k = 3). Multi-field cells feed every backend the same
deterministic ``{field: array}`` mapping (``make_fields``).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax.numpy as jnp

from repro.ir import (
    advection_diffusion_program,
    hdiff_coupled_program,
    hdiff_program,
    jacobi2d_3pt_program,
    jacobi2d_5pt_program,
    jacobi2d_9pt_program,
    laplacian_program,
    lower_pallas,
    lower_reference,
    lower_sharded,
    repeat,
    seidel2d_program,
    shallow_water_program,
    smagorinsky_coeff,
    vadvc_program,
)
from repro.ir.plan import resident_blocks

GRID = (2, 48, 48)
TOL = 1e-6
SEED = 2024

PROGRAMS = {
    "hdiff": lambda: hdiff_program(),
    "hdiff_simple": lambda: hdiff_program(limit=False),
    "jacobi2d_3pt": jacobi2d_3pt_program,
    "laplacian": laplacian_program,
    "jacobi2d_5pt": jacobi2d_5pt_program,
    "jacobi2d_9pt": jacobi2d_9pt_program,
    "seidel2d": seidel2d_program,
    # Multi-field workloads: every backend takes a {field: array} mapping.
    # vadvc exchanges BOTH fields' halos; hdiff_coupled's coeff field is
    # radius 0 at k=1 (no exchange) and grows to 2(k-1) under repeat.
    "vadvc": vadvc_program,
    "hdiff_coupled": lambda: hdiff_coupled_program(),
    # Multi-OUTPUT workloads (coupled systems): backends return a
    # {field: array} dict, compared per output field. shallow_water evolves
    # {u, v, h} through the gravity-wave coupling; advection_diffusion
    # evolves {c, u} over a SHARED radius-0 velocity v (growing to k-1).
    "shallow_water": shallow_water_program,
    "advection_diffusion": advection_diffusion_program,
}

BACKENDS = ("reference", "staged", "pallas", "sharded-reference", "sharded-pallas")
SHARDED_BACKENDS = tuple(b for b in BACKENDS if b.startswith("sharded-"))
KS = (1, 2, 3)
MESHES = ((1, 1), (8, 1), (2, 4), (1, 8))


def mesh_id(mesh_shape: tuple[int, int]) -> str:
    return f"{mesh_shape[0]}x{mesh_shape[1]}"


def devices_needed(backend: str, mesh_shape: tuple[int, int]) -> int:
    if backend in SHARDED_BACKENDS:
        return mesh_shape[0] * mesh_shape[1]
    return 1


def make_input(grid: tuple[int, ...] = GRID, seed: int = SEED):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(grid).astype(np.float32))


def make_fields(name: str, grid: tuple[int, ...] = GRID, seed: int = SEED):
    """The shared input for one program cell: a bare array for single-input
    programs (unchanged from before multi-field landed), a deterministic
    ``{field: array}`` mapping for multi-field ones. A ``coeff`` field gets
    a positive Smagorinsky-style coefficient (0.025 modulated per point)
    instead of raw noise, so repeated diffusion sweeps stay tame."""
    prog = PROGRAMS[name]()
    if len(prog.inputs) == 1:
        return make_input(grid, seed)
    rng = np.random.default_rng(seed)
    fields = {}
    for f in prog.inputs:
        a = rng.standard_normal(grid).astype(np.float32)
        if f == "coeff":
            a = smagorinsky_coeff(a)
        fields[f] = jnp.asarray(a)
    return fields


def iter_cases(mesh_shapes=MESHES):
    """All (program, backend, k, mesh) cells for the given mesh subset.
    Non-sharded backends are mesh-independent: they appear once, at 1x1."""
    for name in PROGRAMS:
        for backend in BACKENDS:
            for k in KS:
                for mesh_shape in mesh_shapes:
                    if backend not in SHARDED_BACKENDS and mesh_shape != (1, 1):
                        continue
                    yield name, backend, k, mesh_shape


# -- batched (ensemble) cells -------------------------------------------------
# The vmap-batched lowering's parity matrix: a deliberately small program
# subset (one single-input chain, one multi-output coupled system, one
# multi-field workload) because every batched cell already runs members x
# backends applications. The contract is two-sided: member i of the batched
# output is BIT-identical to an independent application on the SAME backend,
# and 1e-6-close to the reference oracle.
BATCHED_PROGRAMS = ("hdiff", "shallow_water", "hdiff_coupled")
BATCHED_KS = (1, 2)
BATCHED_MESHES = ((1, 1), (2, 4))
BATCH_MEMBERS = 3


def make_batched_fields(
    name: str, members: int = BATCH_MEMBERS,
    grid: tuple[int, ...] = GRID, seed: int = SEED,
):
    """Member i's initial conditions are ``make_fields(name, seed=SEED+i)``
    — each member is a genuinely distinct perturbation, and the SAME
    per-member inputs drive the unbatched side of every batched cell —
    stacked along a fresh leading member axis."""
    per = [make_fields(name, grid, seed + i) for i in range(members)]
    if isinstance(per[0], dict):
        return {f: jnp.stack([p[f] for p in per]) for f in per[0]}
    return jnp.stack(per)


def member_slice(result, i: int):
    """Member i of a batched result, dict-aware like :func:`to_host`."""
    if isinstance(result, dict):
        return {f: a[i] for f, a in result.items()}
    return result[i]


def build_batched(program, backend: str, mesh_shape: tuple[int, int]):
    """The batched ``{field: (N, *grid)} -> (N, ...)`` callable for one
    cell — same per-backend knobs as :func:`build` so "bit-exact vs the
    same backend" compares identical inner computations."""
    from repro.ir import lower_batched

    return lower_batched(
        program,
        backend=backend,
        mesh_shape=mesh_shape if backend in SHARDED_BACKENDS else None,
        interpret=True if backend == "pallas" else None,
    )


def run_batched_case(
    name: str, backend: str, k: int, mesh_shape, members: int = BATCH_MEMBERS
):
    """(batched, per_member_same_backend, per_member_oracle) for one cell;
    each of the last two is a list of ``members`` results."""
    prog = repeat(PROGRAMS[name](), k)
    batched = to_host(
        build_batched(prog, backend, mesh_shape)(
            make_batched_fields(name, members)
        )
    )
    base = build(prog, backend, mesh_shape)
    seq = [to_host(base(make_fields(name, GRID, SEED + i))) for i in range(members)]
    ref = lower_reference(prog)
    oracles = [
        to_host(ref(make_fields(name, GRID, SEED + i))) for i in range(members)
    ]
    return batched, seq, oracles


def assert_batched_case(
    name: str, backend: str, k: int, mesh_shape, members: int = BATCH_MEMBERS
):
    batched, seq, oracles = run_batched_case(name, backend, k, mesh_shape, members)
    tag = f"{name}/{backend}/k={k}/mesh={mesh_id(mesh_shape)}/N={members}"
    for i in range(members):
        got_i = member_slice(batched, i)
        assert_equal(got_i, seq[i], err_msg=f"{tag}/member={i} (vs same backend)")
        assert_close(got_i, oracles[i], err_msg=f"{tag}/member={i} (vs oracle)")
    return batched


# -- ragged-grid cells ---------------------------------------------------------
# Grids whose rows no fitting aligned tile divides, at a VMEM budget small
# enough that ``lower_pallas`` plans a ``cdiv`` row grid whose last tile
# overhangs the rows (``repro.ir.plan.grid_block_rows``) — the path the
# chip cells' 774- and 390-row grids take. Each grid's budget holds
# ``tile_rows`` rows of the program's resident blocks, as the planner counts
# them, so every program and k in the matrix plans that tile.
#
# ``rows`` has 36 rows (no aligned divisor but the whole dim) on a full
# 128-lane row; ``rows_lanes`` is COSMO-E's class: 39 rows with no aligned
# divisor on 58 lanes, not a multiple of 128. Both take 8-row tiles, so the
# last tile holds 4 or 7 real rows (fewer than the k=3 hdiff chain halo of
# 6), and the neighbour blocks are whole tiles. ``wide`` takes 24-row tiles
# over 77 rows, the last holding 5, so every haloed field fetches 8-row
# halo blocks, the chip cells' case (112- and 40-row tiles). ``whole`` is
# the fallback: at the default budget 36 rows take one whole-dim tile, which
# is neither ragged nor a multiple of 8, so it keeps whole neighbour tiles.
@dataclasses.dataclass(frozen=True)
class RaggedGrid:
    shape: tuple[int, int, int]
    tile_rows: int | None  # the tile the budget is sized for; None: default
    ragged: bool  # the plan's last tile overhangs the rows
    halo_blocks: bool  # haloed fields fetch halo blocks narrower than the tile

    def budget(self, program) -> int | None:
        """``lower_pallas``'s ``vmem_budget`` for ``program`` on this grid."""
        if self.tile_rows is None:
            return None
        radii = program.exchange_radii()
        n_haloed = sum(1 for f in program.inputs if radii[f])
        resident = resident_blocks(
            n_haloed, len(program.inputs) - n_haloed, len(program.outputs)
        )
        return resident * self.tile_rows * self.shape[-1] * 4


RAGGED_GRIDS = {
    "rows": RaggedGrid((2, 36, 128), 8, ragged=True, halo_blocks=False),
    "rows_lanes": RaggedGrid((2, 39, 58), 8, ragged=True, halo_blocks=False),
    "wide": RaggedGrid((2, 77, 128), 24, ragged=True, halo_blocks=True),
    "whole": RaggedGrid((2, 36, 128), None, ragged=False, halo_blocks=False),
}
# The budget of an 8-row tile for every program of the matrix on the
# ``rows`` and ``rows_lanes`` grids (the served-path tests use it).
RAGGED_BUDGET = 16 * 1024
RAGGED_MEMBERS = 2
RAGGED_BUILDS = "ir.lower_pallas.ragged_builds"
HALO_BLOCK_BUILDS = "ir.lower_pallas.halo_block_builds"


def ragged_builds(registry) -> float:
    """The ragged-build counter of a metrics registry (0 when never bumped)."""
    return registry.snapshot()["counters"].get(RAGGED_BUILDS, 0)


def halo_block_builds(registry) -> float:
    """The halo-block-build counter of a metrics registry (0 when never
    bumped)."""
    return registry.snapshot()["counters"].get(HALO_BLOCK_BUILDS, 0)


def assert_plan(registry, grid: RaggedGrid, err_msg: str = ""):
    """The counters of one ragged cell's build say the plan it expects."""
    assert (ragged_builds(registry) >= 1) == grid.ragged, (
        f"{err_msg}: ragged builds {ragged_builds(registry)}, expected "
        f"{'some' if grid.ragged else 'none'}"
    )
    assert (halo_block_builds(registry) >= 1) == grid.halo_blocks, (
        f"{err_msg}: halo-block builds {halo_block_builds(registry)}, expected "
        f"{'some' if grid.halo_blocks else 'none'}"
    )


def build(program, backend: str, mesh_shape: tuple[int, int], *, overlap=False):
    """The lowered ``x -> program(x)`` callable for one matrix cell."""
    if backend == "reference":
        return lower_reference(program)
    if backend == "staged":
        return lower_reference(program, mode="staged")
    if backend == "pallas":
        return lower_pallas(program, interpret=True)
    if backend in SHARDED_BACKENDS:
        return lower_sharded(
            program,
            mesh_shape=mesh_shape,
            inner=backend.removeprefix("sharded-"),
            overlap=overlap,
        )
    raise ValueError(f"unknown conformance backend {backend!r}")


def to_host(result):
    """A lowered result as numpy: a bare ndarray (single-output) or a
    ``{field: ndarray}`` dict (multi-output) — the one conversion every
    harness consumer shares."""
    if isinstance(result, dict):
        return {f: np.asarray(a) for f, a in result.items()}
    return np.asarray(result)


@functools.lru_cache(maxsize=None)
def oracle(name: str, k: int):
    """lower_reference of the k-step composed program on the shared input."""
    prog = repeat(PROGRAMS[name](), k)
    return to_host(lower_reference(prog)(make_fields(name)))


def run_case(name: str, backend: str, k: int, mesh_shape, *, overlap=False):
    """(got, want) for one cell; caller asserts (pytest or subprocess).
    Both sides are bare ndarrays for single-output programs and
    ``{field: ndarray}`` dicts for multi-output ones."""
    prog = repeat(PROGRAMS[name](), k)
    got = to_host(
        build(prog, backend, mesh_shape, overlap=overlap)(make_fields(name))
    )
    return got, oracle(name, k)


def assert_close(got, want, err_msg: str = ""):
    """Tolerance compare, per output field for multi-output results."""
    if isinstance(want, dict):
        assert set(got) == set(want), (
            f"{err_msg}: output fields {sorted(got)} != {sorted(want)}"
        )
        for f in want:
            np.testing.assert_allclose(
                got[f], want[f], rtol=TOL, atol=TOL, err_msg=f"{err_msg}[{f}]"
            )
        return
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL, err_msg=err_msg)


def assert_equal(a, b, err_msg: str = ""):
    """Bitwise compare (the overlap contract), dict-aware like
    :func:`assert_close`."""
    if isinstance(a, dict):
        assert set(a) == set(b), (
            f"{err_msg}: output fields {sorted(a)} != {sorted(b)}"
        )
        for f in a:
            np.testing.assert_array_equal(a[f], b[f], err_msg=f"{err_msg}[{f}]")
        return
    np.testing.assert_array_equal(a, b, err_msg=err_msg)


def assert_case(name: str, backend: str, k: int, mesh_shape, *, overlap=False):
    got, want = run_case(name, backend, k, mesh_shape, overlap=overlap)
    assert_close(
        got,
        want,
        err_msg=f"{name}/{backend}/k={k}/mesh={mesh_id(mesh_shape)}"
        + ("/overlap" if overlap else ""),
    )
    return got


# -- gradient-conformance cells -----------------------------------------------
# The autodiff matrix: jax.grad of every differentiable lowering
# (``build_backend(..., differentiable=True)`` — the derived adjoint
# custom_vjp) must match jax.grad of ``lower_reference`` on a fixed
# random-weighted scalar loss, cell for cell over the SAME programs, ks and
# meshes as the forward matrix. The tolerance is RELATIVE: float32 gradient
# magnitudes grow with k (laplacian k=3 reaches ~60 absolute), so a flat
# atol would miss the ~1e-7 relative agreement the adjoints actually hold.
GRAD_TOL = 1e-5


def make_loss_weights(name: str, k: int):
    """Fixed random weights of the scalar conformance loss
    ``sum(w * y)`` (per output field for coupled systems) — shared by every
    backend cell so the oracle gradient is computed once."""
    ref = oracle(name, k)
    rng = np.random.default_rng(SEED + 7)
    if isinstance(ref, dict):
        return {
            f: jnp.asarray(rng.standard_normal(a.shape).astype(a.dtype))
            for f, a in ref.items()
        }
    return jnp.asarray(rng.standard_normal(ref.shape).astype(ref.dtype))


def grad_loss(fn, w):
    """The cell's scalar loss: fixed-weight contraction of the lowering."""
    import jax.numpy as _jnp

    def loss(x):
        y = fn(x)
        if isinstance(y, dict):
            return sum(_jnp.vdot(w[f], y[f]) for f in y)
        return _jnp.vdot(w, y)

    return loss


def build_grad(program, backend: str, mesh_shape: tuple[int, int]):
    """The differentiable lowered callable for one gradient cell."""
    from repro.ir import build_backend

    return build_backend(
        program,
        backend,
        mesh_shape=mesh_shape if backend in SHARDED_BACKENDS else None,
        interpret=True if backend == "pallas" else None,
        differentiable=True,
    )


@functools.lru_cache(maxsize=None)
def grad_oracle(name: str, k: int):
    """jax.grad of the reference lowering on the shared loss weights."""
    import jax

    prog = repeat(PROGRAMS[name](), k)
    w = make_loss_weights(name, k)
    g = jax.grad(grad_loss(lower_reference(prog), w))(make_fields(name))
    return to_host(g)


def run_grad_case(name: str, backend: str, k: int, mesh_shape):
    """(got, want) gradients for one cell, numpy on both sides."""
    import jax

    prog = repeat(PROGRAMS[name](), k)
    w = make_loss_weights(name, k)
    fn = build_grad(prog, backend, mesh_shape)
    got = jax.grad(grad_loss(fn, w))(make_fields(name))
    return to_host(got), grad_oracle(name, k)


def _assert_rel(got, want, err_msg: str):
    got, want = np.asarray(got), np.asarray(want)
    denom = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got - want).max()) / denom
    assert err <= GRAD_TOL, (
        f"{err_msg}: max relative gradient error {err:.3e} > {GRAD_TOL}"
    )


def assert_grad_close(got, want, err_msg: str = ""):
    """Relative-tolerance gradient compare, per input field for
    multi-field programs."""
    if isinstance(want, dict):
        assert set(got) == set(want), (
            f"{err_msg}: cotangent fields {sorted(got)} != {sorted(want)}"
        )
        for f in want:
            _assert_rel(got[f], want[f], f"{err_msg}[{f}]")
        return
    _assert_rel(got, want, err_msg)


def assert_grad_case(name: str, backend: str, k: int, mesh_shape):
    got, want = run_grad_case(name, backend, k, mesh_shape)
    assert_grad_close(
        got, want,
        err_msg=f"grad/{name}/{backend}/k={k}/mesh={mesh_id(mesh_shape)}",
    )
    return got
