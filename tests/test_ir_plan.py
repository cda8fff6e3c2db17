"""VMEM tile-budget planner tests: shared between kernels and the IR."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.ir.plan import (
    DEFAULT_VMEM_TILE_BUDGET,
    SUBLANES,
    VMEM_BUDGET_ENV,
    grid_block_rows,
    pick_block_rows,
    resident_blocks,
    vmem_tile_budget,
)
from repro.ir import (
    build_backend,
    hdiff_program,
    lower_pallas,
    lower_reference,
    repeat,
    shallow_water_program,
)
from repro.kernels.hdiff import hdiff_fused
from repro.kernels.hdiff.ops import _pick_block_rows
from repro.core import hdiff


def test_budget_resolution_order(monkeypatch):
    monkeypatch.delenv(VMEM_BUDGET_ENV, raising=False)
    assert vmem_tile_budget() == DEFAULT_VMEM_TILE_BUDGET
    monkeypatch.setenv(VMEM_BUDGET_ENV, str(1 << 20))
    assert vmem_tile_budget() == 1 << 20
    # explicit argument wins over the env var
    assert vmem_tile_budget(2048) == 2048
    monkeypatch.setenv(VMEM_BUDGET_ENV, "not-a-number")
    with pytest.raises(ValueError, match=VMEM_BUDGET_ENV):
        vmem_tile_budget()


def test_budget_rejects_non_positive(monkeypatch):
    """REPRO_VMEM_BUDGET=0 (or negative) used to degrade every kernel to
    1-row tiles; it is a configuration error and must raise."""
    monkeypatch.delenv(VMEM_BUDGET_ENV, raising=False)
    with pytest.raises(ValueError, match="positive"):
        vmem_tile_budget(0)
    with pytest.raises(ValueError, match="positive"):
        vmem_tile_budget(-4096)
    monkeypatch.setenv(VMEM_BUDGET_ENV, "0")
    with pytest.raises(ValueError, match=VMEM_BUDGET_ENV):
        vmem_tile_budget()
    monkeypatch.setenv(VMEM_BUDGET_ENV, "-1")
    with pytest.raises(ValueError, match=VMEM_BUDGET_ENV):
        vmem_tile_budget()
    with pytest.raises(ValueError):
        pick_block_rows(256, 256, resident=1, budget_bytes=0)


def test_pick_block_rows_rejects_unsatisfiable_floor():
    """min_rows above every divisor of rows (rows itself) must raise, not
    silently fall back to an undersized tile."""
    with pytest.raises(ValueError, match="min_rows"):
        pick_block_rows(4, 128, resident=1, min_rows=8)
    # rows == min_rows stays legal
    assert pick_block_rows(8, 128, resident=1, min_rows=8) == 8


def test_pick_block_rows_budget_and_floor():
    # 256x256 f32 tile is 256 KiB: fits the 4 MiB default whole.
    assert pick_block_rows(256, 256, resident=1) == 256
    # A 64 KiB budget allows 64 rows of 256 f32 cols.
    assert pick_block_rows(256, 256, resident=1, budget_bytes=64 * 1024) == 64
    # The structural floor is respected even when smaller tiles would fit.
    assert pick_block_rows(256, 256, resident=1, budget_bytes=1024, min_rows=12) == 16
    # Nothing fits: smallest aligned divisor >= min_rows (correctness over
    # budget), and the whole dim when no multiple of 8 divides rows.
    assert pick_block_rows(256, 1 << 20, resident=1, budget_bytes=1024, min_rows=4) == 8
    assert pick_block_rows(12, 1 << 20, resident=1, budget_bytes=1024, min_rows=4) == 12
    assert pick_block_rows(7, 1 << 20, resident=1, budget_bytes=1024, min_rows=2) == 7


def test_pick_block_rows_counts_resident_blocks_and_sublanes(monkeypatch):
    monkeypatch.delenv(VMEM_BUDGET_ENV, raising=False)
    # Three slabs per haloed input, one per plain input and per output, x2.
    assert resident_blocks(1, 0, 1) == 8
    assert resident_blocks(3, 1, 3) == 26
    # The paper grid keeps its one-plane tile: 8 blocks of 256 KiB.
    assert pick_block_rows(256, 256, resident=8) == 256
    # 2048 x 2048 f32: 8 blocks of 64 rows fill the 4 MiB budget exactly.
    assert pick_block_rows(2048, 2048, resident=8) == 64
    # Candidates are multiples of 8 (12 rows would fit the budget)...
    budget = 12 * 1024 * 4
    assert pick_block_rows(48, 1024, resident=1, budget_bytes=budget) == SUBLANES
    # ...or the whole dim, when no multiple of 8 divides it.
    assert pick_block_rows(260, 256, resident=1, budget_bytes=1024) == 260


def test_pick_block_rows_env_override(monkeypatch):
    monkeypatch.setenv(VMEM_BUDGET_ENV, str(64 * 1024))
    assert pick_block_rows(256, 256, resident=1) == 64
    # kernels/hdiff's picker goes through the same budget resolution, and
    # counts its 8 resident blocks (3 input slabs + output, double-buffered).
    assert _pick_block_rows((1, 256, 256)) == 8


def test_hdiff_fused_respects_vmem_budget_argument():
    rng = np.random.default_rng(3)
    psi = jnp.asarray(rng.standard_normal((2, 32, 16)).astype(np.float32))
    want = np.asarray(hdiff(psi, 0.025))
    # 8-row tiles (32*16*4 = 2 KiB budget => 8 rows of 16 cols at 512 B/row).
    got = hdiff_fused(psi, 0.025, interpret=True, vmem_budget=512 * 8)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def test_padded_rows(monkeypatch):
    """``grid_block_rows``, the row tile of a ``cdiv`` grid: a grid that
    ``pick_block_rows`` tiles within the budget keeps that divisor, any
    other gets the fewest fitting aligned tiles, the last one ragged."""
    monkeypatch.delenv(VMEM_BUDGET_ENV, raising=False)
    # A grid whose own tile fits keeps pick_block_rows' divisor (the paper
    # grid, and 2048 rows, whose aligned 64-row divisor fits).
    assert grid_block_rows(256, 256, resident=8) == 256
    assert grid_block_rows(2048, 2048, resident=8) == 64
    assert pick_block_rows(2048, 2048, resident=8) == 64
    # COSMO-1's 774 x 1158 forecast field: 774 rows have no aligned divisor
    # that fits, so seven 112-row tiles, the last one holding 102 rows.
    assert grid_block_rows(774, 1158, resident=8, min_rows=2) == 112
    # The paper grid's adjoint runs on 260 x 260 (radius-2 pad each side):
    # the whole dim is its only aligned tile and 20 such blocks overflow
    # 4 MiB, so two 136-row tiles.
    assert grid_block_rows(260, 260, resident=20, min_rows=2) == 136
    # A 4x1-sharded 2048 grid's halo-padded block (512 + 2 * 2 rows): nine
    # 64-row tiles, the last one holding 4 rows.
    assert grid_block_rows(516, 2048, resident=8, min_rows=2) == 64
    # Tiles never drop below the kernel's floor, nor exceed the rows.
    assert grid_block_rows(20, 1 << 16, resident=1, budget_bytes=1024, min_rows=10) == 16
    assert grid_block_rows(20, 1 << 16, resident=1, budget_bytes=1024, min_rows=18) == 20


def _pad_case(name, k, rows=18):
    rng = np.random.default_rng(11)
    shape = (2, rows, 128)  # no multiple of 8 divides 18 or 17 (nor 22, 21)
    if name == "hdiff":
        program = repeat(hdiff_program(), k)
        x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    else:
        program = repeat(shallow_water_program(), k)
        x = {
            f: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
            for f in program.inputs
        }
    # Room for 8 rows of the forward's resident blocks: the whole dim
    # overflows, so the grid runs three 8-row tiles, the last one ragged.
    resident = resident_blocks(len(program.inputs), 0, len(program.outputs))
    budget = resident * 8 * shape[-1] * 4
    assert grid_block_rows(
        rows, shape[-1], resident=resident, budget_bytes=budget,
        min_rows=program.radius,
    ) == 8
    return program, x, budget


def _assert_matches_reference(program, x, budget, atol):
    got = lower_pallas(program, vmem_budget=budget, interpret=True)(x)
    want = lower_reference(program)(x)
    if isinstance(want, dict):
        assert set(got) == set(want)
        for f in want:
            np.testing.assert_allclose(got[f], want[f], rtol=0, atol=atol)
    else:
        np.testing.assert_allclose(got, want, rtol=0, atol=atol)


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("name", ["hdiff", "shallow_water"])
def test_lower_pallas_padded_rows_match_reference(name, k):
    """The rows past the last tile's end are never read into a kept point:
    the result of the ragged grid equals the reference."""
    _assert_matches_reference(*_pad_case(name, k), atol=1e-6)


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("name", ["hdiff", "shallow_water"])
def test_lower_pallas_one_row_last_tile_matches_reference(name, k):
    """17 rows in 8-row tiles: the last tile holds one real row, no more than
    the chain halo, and its neighbour's ``next`` slab reaches past the end.
    Interpret mode fills the overhang with NaN, so any leak would show."""
    program, x, budget = _pad_case(name, k, rows=17)
    assert program.radius >= 17 % 8
    _assert_matches_reference(program, x, budget, atol=0)


def test_padded_rows_gradient_matches_reference():
    """The adjoint kernel's grid (18 + 2 * 2 = 22 rows) runs ragged too."""
    program, x, budget = _pad_case("hdiff", 1)
    fwd = build_backend(
        program, "pallas", differentiable=True, vmem_budget=budget, interpret=True
    )
    ref = lower_reference(program)
    got = jax.grad(lambda a: jnp.sum(fwd(a) ** 2))(x)
    want = jax.grad(lambda a: jnp.sum(ref(a) ** 2))(x)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err <= 1e-5, err
