"""Ragged-grid conformance of the fused Pallas lowering.

Every program of the matrix at every k, on every ``RAGGED_GRIDS`` entry,
lowered by ``lower_pallas`` at the grid's budget: the plan must be the one
the grid expects (the ``ir.lower_pallas.ragged_builds`` counter moves for
a ragged ``cdiv`` row grid, ``ir.lower_pallas.halo_block_builds`` for halo
blocks narrower than the tile, and neither on the whole-dim fallback), and
the result must match ``lower_reference`` of the same composed program at
the matrix tolerance. The batched (ensemble) cells of the same grids live
in ``test_conformance_ragged_batched.py``.
"""

import pytest

from conformance import (
    KS,
    PROGRAMS,
    RAGGED_GRIDS,
    assert_close,
    assert_plan,
    make_fields,
    to_host,
)
from repro.ir import lower_pallas, lower_reference, repeat
from repro.obs import metrics


@pytest.mark.parametrize("grid_id", list(RAGGED_GRIDS))
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", list(PROGRAMS))
def test_ragged_pallas_matches_reference(name, k, grid_id):
    grid = RAGGED_GRIDS[grid_id]
    prog = repeat(PROGRAMS[name](), k)
    x = make_fields(name, grid.shape)
    fn = lower_pallas(prog, interpret=True, vmem_budget=grid.budget(prog))
    tag = f"{name}/pallas/k={k}/grid={grid_id}"
    with metrics.using() as registry:
        got = to_host(fn(x))
        assert_plan(registry, grid, err_msg=tag)
    want = to_host(lower_reference(prog)(x))
    assert_close(got, want, err_msg=tag)
