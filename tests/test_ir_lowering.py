"""IR-lowering tests: paper-grid acceptance, compound policies, validation.

Per-backend/per-program parity cells live in the cross-backend conformance
matrix (tests/conformance.py + tests/test_conformance_matrix.py) — this
file keeps only what the matrix does not cover: the paper-grid acceptance
run, the CompoundStencil policy wrappers, the planners, and the lowering
argument validation (including the 2-D mesh arguments).
"""

import collections
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    hdiff,
    make_hdiff_compound,
    plan_partition,
)
from repro.ir import (
    ELEMENTARY_PROGRAMS,
    StencilProgram,
    affine,
    hdiff_fields_program,
    hdiff_program,
    jacobi1d_program,
    lower_pallas,
    lower_reference,
    lower_sharded,
    shallow_water_program,
)
from repro.ir import plan_partition as plan_partition_2d
from repro.launch.mesh import make_mesh
from repro.obs import metrics

RNG = np.random.default_rng(11)


def _grid(*shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


# --- paper-grid acceptance ----------------------------------------------------


def test_hdiff_all_backends_on_paper_grid():
    """Acceptance: IR-lowered hdiff matches core.hdiff to 1e-6 on the
    paper's 64x256x256 domain (reference + Pallas interpret here; the
    8-device sharded run lives in tests/multidev/_ir_check.py)."""
    x = _grid(64, 256, 256)
    want = np.asarray(hdiff(x, 0.025))
    prog = hdiff_program()
    got_ref = np.asarray(lower_reference(prog)(x))
    np.testing.assert_allclose(got_ref, want, rtol=1e-6, atol=1e-6)
    got_pl = np.asarray(lower_pallas(prog, interpret=True)(x))
    np.testing.assert_allclose(got_pl, want, rtol=1e-6, atol=1e-6)


# --- 1-D programs (outside the 2-D conformance matrix) ------------------------


def test_jacobi1d_program_matches_handwritten():
    from repro.core import ELEMENTARY_FNS

    prog = ELEMENTARY_PROGRAMS["jacobi1d"]()
    x = _grid(4, 16)
    want = np.asarray(ELEMENTARY_FNS["jacobi1d"](x))
    for tag, fn in [
        ("fused", lower_reference(prog)),
        ("staged", lower_reference(prog, mode="staged")),
        ("pallas", lower_pallas(prog, interpret=True)),
    ]:
        got = np.asarray(fn(x))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6, err_msg=tag)


# --- compound policies are thin wrappers over the lowerings -------------------


def test_compound_fused_pallas_policy_now_works():
    x = _grid(2, 16, 12)
    comp = make_hdiff_compound(coeff=0.025, limit=True)
    want = np.asarray(hdiff(x, 0.025))
    for policy in ("fused-xla", "staged", "fused-pallas"):
        got = np.asarray(comp.apply(x, policy=policy))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6, err_msg=policy)
    with pytest.raises(ValueError, match="unknown policy"):
        comp.apply(x, policy="nope")


def test_compound_multi_input_program_runs_all_policies():
    """Multi-input DAGs run every policy now that lower_pallas takes a
    field mapping — including fused-pallas (one ref per field)."""
    from repro.core.compound import CompoundStencil

    prog = StencilProgram(
        "sum2", ["a", "b"],
        [affine("s_a", "a", {(0, 0): 1.0}),
         affine("out", "s_a", {(0, 0): 1.0})],
    )
    comp = CompoundStencil("sum2", prog)
    x = {"a": _grid(2, 8, 8), "b": _grid(2, 8, 8)}
    for policy in ("fused-xla", "staged", "fused-pallas"):
        got = np.asarray(comp.apply(x, policy=policy))
        np.testing.assert_allclose(
            got, np.asarray(x["a"]), rtol=0, atol=0, err_msg=policy
        )


def test_compound_accounting_is_graph_derived():
    comp = make_hdiff_compound()
    assert comp.radius == 2
    assert comp.total_flops(10) == 10 * 72  # 2*26 + 20 per point
    lap = next(s for s in comp.stages if s.name == "lap")
    assert (lap.macs, lap.evaluations) == (5, 5)


def test_plan_partition_accepts_program():
    prog = hdiff_program()
    plan = plan_partition(64, 256, 256, 8, program=prog)
    default = plan_partition(64, 256, 256, 8)
    assert plan == default  # hdiff defaults ARE the derived program numbers
    assert plan.halo == 2
    # A radius-1 program plans with a thinner halo.
    plan1 = plan_partition(64, 256, 256, 8, program=ELEMENTARY_PROGRAMS["laplacian"]())
    assert plan1.halo == 1


def test_plan_partition_2d_minimizes_wire_bytes():
    from repro.dist import halo_exchange_bytes
    from repro.ir import repeat

    prog = hdiff_program()
    plan = plan_partition_2d(prog, 64, 256, 256, 8)
    assert plan.row_shards * plan.col_shards == 8
    assert plan.halo == prog.radius == 2
    # Never worse than the 1-D row baseline; on the square paper grid the
    # balanced split strictly beats it (less boundary surface).
    baseline = halo_exchange_bytes(64, 256, 256, 8, halo=2)
    assert plan.wire_bytes < baseline
    assert plan.mesh_shape == (plan.row_shards, plan.col_shards)
    # Chain radius drives the feasibility floor and the band depth.
    plan3 = plan_partition_2d(repeat(prog, 3), 64, 256, 256, 8)
    assert plan3.halo == 6


def test_plan_partition_2d_rescues_fine_row_mesh():
    """rows/n < halo makes the 1-D row split infeasible — the planner
    routes the excess shards to columns (the fine-mesh error's remedy)."""
    prog = hdiff_program()
    plan = plan_partition_2d(prog, 8, 16, 256, 16)
    assert plan.col_shards > 1
    assert plan.row_shards * plan.col_shards == 16
    with pytest.raises(ValueError, match="factorization"):
        plan_partition_2d(hdiff_program(), 8, 4, 4, 64)


# --- lowering validation ------------------------------------------------------


def test_lower_pallas_rejects_bad_inputs():
    prog = hdiff_program()
    fn = lower_pallas(prog, interpret=True)
    with pytest.raises(ValueError, match="depth, rows, cols"):
        fn(_grid(8, 8))
    with pytest.raises(ValueError, match="not divisible"):
        lower_pallas(prog, block_rows=5, interpret=True)(_grid(2, 16, 12))
    two_in = StencilProgram(
        "two", ["a", "b"], [affine("out", "a", {(0, 0): 1.0})]
    )
    # Multi-input programs lower fine now, but demand a complete mapping.
    fn2 = lower_pallas(two_in, interpret=True)
    with pytest.raises(ValueError, match="pass a mapping"):
        fn2(_grid(2, 8, 8))
    with pytest.raises(ValueError, match="missing"):
        fn2({"a": _grid(2, 8, 8)})
    with pytest.raises(ValueError, match="share one grid"):
        fn2({"a": _grid(2, 8, 8), "b": _grid(2, 8, 16)})


# --- names in traces -----------------------------------------------------------


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _pallas_eqns(sub)


@pytest.mark.parametrize("prog,shape", [
    (hdiff_program(), (1, 36, 128)),                  # one output, padded rows
    (shallow_water_program(), (1, 16, 24)),           # coupled outputs
    (jacobi1d_program(), (8, 32)),                    # the 1-D kernel
], ids=["hdiff", "shallow_water", "jacobi1d"])
def test_pallas_call_carries_the_program_name(prog, shape):
    fn = lower_pallas(prog, interpret=True, vmem_budget=32768)
    x = _grid(*shape)
    args = {f: x for f in prog.inputs} if len(prog.inputs) > 1 else x
    jaxpr = jax.make_jaxpr(lambda a: fn(a))(args)
    names = [e.params["name"] for e in _pallas_eqns(jaxpr.jaxpr)]
    assert names == [f"ir_{prog.name}"]


@pytest.mark.parametrize("prog,shape,blocks", [
    # COSMO-1: 774 rows in 112-row tiles, one haloed field.
    (hdiff_program(), (80, 774, 1158), {(1, 112, 1158): 1, (1, 8, 1158): 2}),
    # COSMO-E: five haloed fields, 390 rows in 40-row tiles.
    (hdiff_fields_program(("u", "v", "w", "t", "pp"), (0.025,) * 5),
     (60, 390, 582), {(1, 40, 582): 5, (1, 8, 582): 10}),
], ids=["cosmo1", "cosmoe"])
def test_neighbour_blocks_are_8_row_halo_blocks(prog, shape, blocks):
    """At the chip cells' shapes each haloed field is fetched as its tile and
    two 8-row halo blocks, not three whole tiles. Traced only, nothing
    runs."""
    fn = lower_pallas(prog)
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    args = {f: x for f in prog.inputs} if len(prog.inputs) > 1 else x
    (eqn,) = _pallas_eqns(jax.make_jaxpr(lambda a: fn(a))(args).jaxpr)
    mapping = eqn.params["grid_mapping"]
    got = collections.Counter(
        bm.transformed_block_aval.shape
        for bm in mapping.block_mappings[: mapping.num_inputs]
        if bm.array_aval.shape == shape
    )
    assert got == blocks


def test_pad_and_crop_sit_under_program_scopes():
    """A grid with no fitting aligned tile (36 rows at a 32 KiB budget) runs
    a ragged ``cdiv`` grid: no pad before the kernel, no crop after it, and
    one ragged build counted; an evenly tiled 32-row grid counts none.

    Interpret mode emulates the last tile's overhang with a pad of its own
    inside the kernel's scope (``ir_hdiff/``); the TPU program holds no pad
    at all (``tests/test_tpu_compile.py``)."""
    fn = lower_pallas(hdiff_program(), interpret=True, vmem_budget=32768)
    with metrics.using() as registry:
        text = jax.jit(lambda a: fn(a)).lower(_grid(1, 36, 128)).compile().as_text()
        assert registry.snapshot()["counters"]["ir.lower_pallas.ragged_builds"] == 1
    pads = [ln for ln in text.splitlines() if re.search(r"\bpad\(", ln)]
    assert all('/ir_hdiff/' in ln for ln in pads), pads
    assert not re.search(r"ir/hdiff/(pad|crop)", text)
    with metrics.using() as registry:
        jax.jit(lambda a: fn(a)).lower(_grid(1, 32, 128)).compile()
        counters = registry.snapshot()["counters"]
    assert counters.get("ir.lower_pallas.ragged_builds", 0) == 0


def test_lower_sharded_validates_axes_and_shapes():
    mesh = make_mesh((1, 1), ("data", "model"))
    prog = hdiff_program()
    with pytest.raises(ValueError, match="no axis"):
        lower_sharded(prog, mesh, depth_axis="nope")
    with pytest.raises(ValueError, match="distinct"):
        lower_sharded(prog, mesh, depth_axis="data", row_axis="data")
    with pytest.raises(ValueError, match="distinct"):
        lower_sharded(prog, mesh, depth_axis=None, row_axis="data", col_axis="data")
    with pytest.raises(ValueError, match="inner backend"):
        lower_sharded(prog, mesh, inner="cuda")
    fn = lower_sharded(prog, mesh)
    with pytest.raises(ValueError, match="depth, rows, cols"):
        fn(_grid(4, 4))


def test_lower_sharded_mesh_shape_argument():
    prog = hdiff_program()
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="not both"):
        lower_sharded(prog, mesh, mesh_shape=(1, 1))
    with pytest.raises(ValueError, match="mesh"):
        lower_sharded(prog)
    # mesh_shape fixes the axis names: explicit axis args are a conflict,
    # not silently ignored.
    with pytest.raises(ValueError, match="don't pass"):
        lower_sharded(prog, mesh_shape=(1, 1), row_axis="model")
    with pytest.raises(ValueError, match="don't pass"):
        lower_sharded(prog, mesh_shape=(1, 1), depth_axis="data")
    # mesh_shape builds its own ("rows", "cols") mesh; 1x1 runs anywhere.
    x = _grid(2, 12, 12)
    fn = lower_sharded(prog, mesh_shape=(1, 1), inner="reference")
    np.testing.assert_allclose(
        np.asarray(fn(x)), np.asarray(hdiff(x, 0.025)), rtol=1e-6, atol=1e-6
    )


def test_exchange_band_checks_name_the_remedy():
    """The fine-mesh halo errors (rows/shard or cols/shard < halo) point at
    sharding the OTHER grid axis — the remedy the README documents. The
    checks are static shape checks, so no multi-device mesh is needed here;
    the in-shard_map raises are covered by tests/multidev/_ir_check.py."""
    import jax.numpy as jnp2

    from repro.dist import exchange_halos_2d, exchange_row_halos

    with pytest.raises(ValueError, match="shard the other grid axis"):
        exchange_row_halos(jnp2.zeros((2, 1, 8)), "rows", 4, halo=2)
    with pytest.raises(ValueError, match="cols/shard 2 < halo 4"):
        exchange_halos_2d(jnp2.zeros((2, 8, 2)), "rows", "cols", 1, 4, halo=4)
