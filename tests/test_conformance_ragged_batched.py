"""Ragged-grid conformance of the vmapped Pallas lowering (the served
cells' path).

The cells of ``test_conformance_ragged.py`` through ``lower_batched(...,
backend="pallas")`` over ``RAGGED_MEMBERS`` members: the plan must be the
one the grid expects (ragged row grid, halo blocks), each member of the
batched output must be bit-identical to the unbatched ``lower_pallas``
call on that member's fields, and match ``lower_reference`` at the matrix
tolerance.
"""

import pytest

from conformance import (
    KS,
    PROGRAMS,
    RAGGED_GRIDS,
    RAGGED_MEMBERS,
    SEED,
    assert_close,
    assert_equal,
    assert_plan,
    make_batched_fields,
    make_fields,
    member_slice,
    to_host,
)
from repro.ir import lower_batched, lower_pallas, lower_reference, repeat
from repro.obs import metrics


@pytest.mark.parametrize("grid_id", list(RAGGED_GRIDS))
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", list(PROGRAMS))
def test_ragged_batched_pallas_matches_members(name, k, grid_id):
    grid = RAGGED_GRIDS[grid_id]
    prog = repeat(PROGRAMS[name](), k)
    budget = grid.budget(prog)
    batched = lower_batched(prog, backend="pallas", interpret=True, vmem_budget=budget)
    with metrics.using() as registry:
        got = to_host(batched(make_batched_fields(name, RAGGED_MEMBERS, grid.shape)))
        assert_plan(registry, grid, err_msg=f"{name}/batched-pallas/k={k}/grid={grid_id}")
    single = lower_pallas(prog, interpret=True, vmem_budget=budget)
    ref = lower_reference(prog)
    for i in range(RAGGED_MEMBERS):
        x = make_fields(name, grid.shape, SEED + i)
        tag = f"{name}/batched-pallas/k={k}/grid={grid_id}/member={i}"
        got_i = member_slice(got, i)
        assert_equal(got_i, to_host(single(x)), err_msg=f"{tag} (vs unbatched pallas)")
        assert_close(got_i, to_host(ref(x)), err_msg=f"{tag} (vs oracle)")
