"""Ragged-grid conformance of the fused Pallas lowering.

Every program of the matrix at every k, on both ``RAGGED_GRIDS``, lowered
by ``lower_pallas`` at ``RAGGED_BUDGET``: the plan must take the ragged
``cdiv`` row grid (the ``ir.lower_pallas.ragged_builds`` counter moves),
and the result must match ``lower_reference`` of the same composed
program at the matrix tolerance. The batched (ensemble) cells of the same
grids live in ``test_conformance_ragged_batched.py``.
"""

import pytest

from conformance import (
    KS,
    PROGRAMS,
    RAGGED_BUDGET,
    RAGGED_GRIDS,
    assert_close,
    make_fields,
    ragged_builds,
    to_host,
)
from repro.ir import lower_pallas, lower_reference, repeat
from repro.obs import metrics


@pytest.mark.parametrize("grid_id", list(RAGGED_GRIDS))
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", list(PROGRAMS))
def test_ragged_pallas_matches_reference(name, k, grid_id):
    prog = repeat(PROGRAMS[name](), k)
    x = make_fields(name, RAGGED_GRIDS[grid_id])
    fn = lower_pallas(prog, interpret=True, vmem_budget=RAGGED_BUDGET)
    with metrics.using() as registry:
        got = to_host(fn(x))
        assert ragged_builds(registry) >= 1, "the plan fell back to an even grid"
    want = to_host(lower_reference(prog)(x))
    assert_close(got, want, err_msg=f"{name}/pallas/k={k}/grid={grid_id}")
