"""Several diffused fields per forecast: ``hdiff_fields_program`` (one hdiff
chain per field, one coefficient each, one multi-output program) against
its plain reference ``repro.core.hdiff_fields``, and served as one request
per member through ``ForecastServer``.

The grid is (2, 39, 58): 39 rows have no divisor that is a multiple of 8
and 58 lanes are not a multiple of 128, as COSMO-E's 390 x 582 grid, so
the Pallas paths run the row pad and crop (under ``vmap`` when batched).
The reference is jitted, so XLA fuses it as it fuses the lowerings, and
every backend agrees with it bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conformance import assert_equal, to_host
from repro.core import hdiff_fields
from repro.ir import (
    hdiff_fields_program,
    hdiff_program,
    lower_batched,
    lower_pallas,
    lower_reference,
)
from repro.ir.plan import grid_block_rows, pick_block_rows, resident_blocks
from repro.obs import metrics
from repro.serve import ForecastServer

NAMES = ("u", "v", "w", "t", "pp")
COEFFS = (0.025, 0.02, 0.03, 0.01, 0.025)
GRID = (2, 39, 58)
SEED = 1513


def _member(seed, grid=GRID):
    rng = np.random.default_rng(seed)
    return {f: jnp.asarray(rng.standard_normal(grid).astype(np.float32))
            for f in NAMES}


def _want(fields, coeffs=COEFFS, limit=True):
    ref = jax.jit(lambda x: hdiff_fields(x, dict(zip(NAMES, coeffs)), limit=limit))
    return to_host(ref(fields))


def _batched(members):
    return {f: jnp.stack([m[f] for m in members]) for f in NAMES}


LOWERINGS = {"reference": lower_reference, "pallas": lower_pallas}


def test_program_is_one_hdiff_chain_per_field():
    p = hdiff_fields_program(NAMES, COEFFS)
    assert p.name == "hdiff_fields" and p.inputs == NAMES
    assert p.outputs == {f: f"out_{f}" for f in NAMES}
    assert p.radius == 2 and p.exchange_radii() == dict.fromkeys(NAMES, 2)
    one = hdiff_program().spec()
    spec = p.spec()
    assert (spec.macs, spec.other_ops, spec.reads) == (
        5 * one.macs, 5 * one.other_ops, 5 * one.reads)
    # Each chain bakes its own coefficient into the structure.
    assert hdiff_fields_program(NAMES, (0.025,) * 5) != p
    with pytest.raises(ValueError, match="coefficients"):
        hdiff_fields_program(NAMES, COEFFS[:4])


@pytest.mark.parametrize("limit", [True, False], ids=["limited", "simple"])
@pytest.mark.parametrize("backend", sorted(LOWERINGS))
def test_lowerings_match_the_plain_reference(backend, limit):
    fields = _member(SEED)
    got = LOWERINGS[backend](hdiff_fields_program(NAMES, COEFFS, limit=limit))(fields)
    assert_equal(to_host(got), _want(fields, limit=limit), err_msg=backend)


def test_batched_pallas_matches_the_plain_reference():
    members = [_member(SEED + i) for i in range(3)]
    got = lower_batched(hdiff_fields_program(NAMES, COEFFS), backend="pallas")(
        _batched(members))
    for i, m in enumerate(members):
        assert_equal({f: np.asarray(v[i]) for f, v in got.items()}, _want(m),
                      err_msg=f"member {i}")


@pytest.mark.parametrize("backend", sorted(LOWERINGS))
def test_a_wrong_coefficient_on_one_field_is_caught(backend):
    """A program whose ``t`` chain diffuses with another coefficient
    differs from the reference on ``t`` alone."""
    fields = _member(SEED)
    planted = tuple(0.05 if f == "t" else c for f, c in zip(NAMES, COEFFS))
    got = to_host(LOWERINGS[backend](hdiff_fields_program(NAMES, planted))(fields))
    want = _want(fields)
    for f in NAMES:
        same = np.array_equal(got[f], want[f])
        assert same == (f != "t"), f


def test_cosmoe_member_plans_padded_40_row_tiles():
    """Five haloed inputs and five outputs keep 40 blocks in VMEM, so at 582
    lanes the 4 MiB budget takes 40-row tiles; 390 rows have no aligned
    divisor, so ten 40-row tiles, the last one holding 30 rows."""
    resident = resident_blocks(5, 0, 5)
    assert resident == 40
    assert pick_block_rows(390, 582, resident=resident) == 390  # whole dim only
    assert grid_block_rows(390, 582, resident=resident, min_rows=2) == 40


def test_served_batches_of_3_and_2_bit_match_unbatched_calls():
    """Five members, one request each, at ``max_batch=3``: a batch of 3 and
    one of 2, each member's result equal to its own unbatched call."""
    prog = hdiff_fields_program(NAMES, COEFFS)
    members = [_member(SEED + i) for i in range(5)]
    srv = ForecastServer(backend="pallas", max_batch=3)
    with metrics.using() as registry:
        rids = [srv.submit(prog, m) for m in members]
        done = {r.rid: r for r in srv.run_until_idle()}
        done = [done[rid] for rid in rids]
    assert sorted(k.batch for k in srv.cache.keys()) == [2, 3]
    single = lower_pallas(prog)
    for i, (r, m) in enumerate(zip(done, members)):
        assert not r.failed
        assert_equal(to_host(r.result), to_host(single(m)), err_msg=f"member {i}")
    # Every member's five fields read and five written.
    field_bytes = 5 * 2 * 5 * members[0]["u"].nbytes
    assert srv.stats["field_bytes"] == field_bytes
    assert registry.snapshot()["counters"]["serve.forecast.field_bytes"] == field_bytes
