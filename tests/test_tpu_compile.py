"""Compile rehearsal for a described TPU v5e: the forecast path's kernels at
real sizes, compiled by the TPU compiler without a chip attached.

Interpret-mode tests cannot see what Mosaic refuses (an in-kernel scatter,
a block that breaks the (8, 128) rule, more VMEM than a kernel may use).
These compiles can. Every case passes ``interpret=False`` explicitly (the
test process's backend is the CPU), and asserts that the compiled HLO holds
the Pallas kernel (``tpu_custom_call``) and that ``memory_analysis()`` fits
one chip's HBM. Nothing runs, so results and times are out of scope here.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler's library, and every test worker
imports every test file.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.hdiff import CONFIG
from repro.ir import (
    build_backend,
    hdiff_coupled_program,
    hdiff_fields_program,
    hdiff_program,
    jacobi1d_program,
    lower_batched,
    lower_pallas,
    lower_sharded,
    repeat,
    shallow_water_program,
)
from repro.kernels.hdiff.kernel import hdiff_pallas
from repro.kernels.hdiff.ops import _pick_block_rows
from repro.kernels.stencil2d.kernel import jacobi1d_pallas
from repro.serve.forecast import _marshal

PAPER_GRID = (CONFIG.depth, CONFIG.rows, CONFIG.cols)  # 64 x 256 x 256
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without one; keep the cache out of it.
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fields(program, shape, sharding):
    if len(program.inputs) == 1:
        return _shape(shape, sharding)
    return {f: _shape(shape, sharding) for f in program.inputs}


def _compile_checked(fn, arg):
    compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the HLO"
    ma = compiled.memory_analysis()
    used = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    assert 0 < used <= V5E_HBM_BYTES, used
    return compiled


@pytest.mark.parametrize("k", [1, 2, 4])
def test_hdiff_k_compiles(one_chip, k):
    fn = lower_pallas(repeat(hdiff_program(), k), interpret=False)
    _compile_checked(fn, _shape(PAPER_GRID, one_chip))


@pytest.mark.parametrize("make", [shallow_water_program, hdiff_coupled_program],
                         ids=["shallow_water", "hdiff_coupled"])
def test_multifield_compiles(one_chip, make):
    program = make()
    fn = lower_pallas(program, interpret=False)
    _compile_checked(fn, _fields(program, PAPER_GRID, one_chip))


def test_hdiff_gradient_compiles(one_chip):
    fwd = build_backend(hdiff_program(), "pallas", differentiable=True, interpret=False)
    grad = jax.grad(lambda x: jnp.sum(fwd(x) ** 2))
    _compile_checked(grad, _shape(PAPER_GRID, one_chip))


def test_batched_ensemble_compiles(one_chip):
    fn = lower_batched(hdiff_program(), backend="pallas", interpret=False)
    _compile_checked(fn, _shape((8,) + PAPER_GRID, one_chip))


def test_handwritten_hdiff_compiles(one_chip):
    br = _pick_block_rows(PAPER_GRID)
    assert br == CONFIG.rows  # the paper grid's tile: one whole plane
    _compile_checked(
        lambda x: hdiff_pallas(x, 0.025, block_rows=br, interpret=False),
        _shape(PAPER_GRID, one_chip),
    )


def test_sharded_2x2_step_compiles(topo):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("rows", "cols"))
    fn = lower_sharded(
        hdiff_program(), mesh, depth_axis=None, row_axis="rows", col_axis="cols",
        inner="pallas", interpret=False,
    )
    compiled = _compile_checked(
        fn, _shape(PAPER_GRID, NamedSharding(mesh, P(None, "rows", "cols")))
    )
    assert "collective-permute" in compiled.as_text()


def test_large_grid_fits_vmem(one_chip):
    """8 x 2048 x 2048: the planner must count every resident block (three
    slabs, the output, double-buffered) or Mosaic runs out of VMEM."""
    fn = lower_pallas(hdiff_program(), interpret=False)
    _compile_checked(fn, _shape((8, 2048, 2048), one_chip))


def test_large_grid_gradient_fits_vmem(one_chip):
    """The gradient's kernels run on the halo-padded 2052 x 2052 grid, whose
    only aligned tile is the whole dim: the rows are padded to fitting
    8-aligned tiles instead."""
    fwd = build_backend(hdiff_program(), "pallas", differentiable=True, interpret=False)
    grad = jax.grad(lambda x: jnp.sum(fwd(x) ** 2))
    _compile_checked(grad, _shape((8, 2048, 2048), one_chip))


@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)], ids=["4x1", "2x2"])
def test_large_grid_sharded_fits_vmem(topo, mesh_shape):
    """A shard's halo-padded block (e.g. 512 + 2 * 2 rows on 4x1) pads its
    rows to fitting tiles like the gradient's grid."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(mesh_shape), ("rows", "cols"))
    fn = lower_sharded(
        hdiff_program(), mesh, depth_axis=None, row_axis="rows", col_axis="cols",
        inner="pallas", interpret=False,
    )
    _compile_checked(
        fn, _shape((8, 2048, 2048), NamedSharding(mesh, P(None, "rows", "cols")))
    )


@pytest.mark.parametrize("which", ["ir", "handwritten"])
def test_1d_kernel_block_compiles(one_chip, which):
    """1-D kernels block whole rows in multiples of 8 (the (8, 128) rule)."""
    if which == "ir":
        fn = lower_pallas(repeat(jacobi1d_program(), 2), interpret=False)
    else:
        def fn(x):
            return jacobi1d_pallas(x, interpret=False)
    _compile_checked(fn, _shape((64, 4096), one_chip))


def test_cosmoe_member_batch_compiles(one_chip):
    """The served call of a batch of 8 COSMO-E members (five 60 x 390 x 582
    fields each): member stack, the five-field kernel's ten 40-row tiles at
    582 lanes under vmap, the last one ragged, and slices. No row pad."""
    program = hdiff_fields_program(("u", "v", "w", "t", "pp"), (0.025,) * 5)
    call = _marshal(lower_batched(program, backend="pallas", interpret=False),
                    program.inputs)
    members = tuple(_fields(program, (60, 390, 582), one_chip) for _ in range(8))
    compiled = _compile_checked(call, members)
    assert "pad(" not in compiled.as_text()


def test_cosmo1_forecast_scan_compiles(one_chip):
    """The forecast cell's call: a ``lax.scan`` of hdiff steps over the
    80 x 774 x 1158 COSMO-1 field. 774 rows run seven 112-row tiles, the
    last one ragged, with no pad before the kernel and no crop after it."""
    step = lower_pallas(hdiff_program(), interpret=False)

    def call(x):
        return jax.lax.scan(lambda s, _: (step(s), None), x, None, length=4)[0]

    compiled = _compile_checked(call, _shape((80, 774, 1158), one_chip))
    assert "pad(" not in compiled.as_text()


COSMO1E_GRID = (80, 774, 1158)
COSMO1E_MEMBERS = 11


@pytest.mark.parametrize("batch", [8, 3])
def test_cosmo1e_served_batch_compiles_on_2x2(topo, batch):
    """The COSMO-1E cell's served calls on a 2x2 mesh: ``batch`` members of
    five 80 x 774 x 1158 fields (11 members at the default ``max_batch``
    serve batches of 8 and 3), each member's fields split into 387 x 579
    blocks, through the sharded-pallas path ``lower_batched`` builds (the
    mesh of described chips in place of the default devices). The batch's
    temporaries must stay within 11 GB a chip, beside its own members
    (arguments and results) and the rest of the ensemble; a batch of 8
    reckons 10.28 GB of temporaries and a peak of 14.08 GB, which counts
    its members' arguments and results."""
    program = hdiff_fields_program(("u", "v", "w", "t", "pp"), (0.025,) * 5)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("rows", "cols"))
    sharding = NamedSharding(mesh, P(None, "rows", "cols"))
    step = lower_sharded(program, mesh, depth_axis=None, row_axis="rows", col_axis="cols",
                         inner="pallas", interpret=False)
    call = _marshal(jax.jit(jax.vmap(step)), program.inputs)
    members = tuple(_fields(program, COSMO1E_GRID, sharding) for _ in range(batch))
    compiled = _compile_checked(call, members)
    text = compiled.as_text()
    assert "collective-permute" in text
    ma = compiled.memory_analysis()
    per_member = ma.argument_size_in_bytes // batch
    print(f"cosmo1e batch {batch}: temp_size_in_bytes {ma.temp_size_in_bytes}, "
          f"peak_memory_in_bytes {ma.peak_memory_in_bytes}, {per_member} B a member")
    assert ma.temp_size_in_bytes <= 11e9, ma.temp_size_in_bytes
    assert ma.peak_memory_in_bytes + (COSMO1E_MEMBERS - batch) * per_member <= V5E_HBM_BYTES
    for out in compiled.output_shardings:
        for s in out.values():
            assert s.is_equivalent_to(sharding, 3), s
