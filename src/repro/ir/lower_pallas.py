"""Fused-Pallas lowering: generic VMEM-resident tile codegen for IR programs.

Generalises the hand-fused hdiff kernel (``repro.kernels.hdiff.kernel``) to
any 2-D program: one program instance owns one row-tile of one plane; the
row halo comes from two halo blocks (each haloed input is passed three
times: its own ``block_rows`` tile, and the ``hb``-row blocks just above and
just below it, clamped at the edges), and the whole DAG is evaluated in
VMEM by ``interior_eval`` — intermediates never touch HBM, the paper's
accumulator-residency discipline. ``hb`` is the smallest multiple of 8 (the
sublane tile) that covers the field's halo and divides the tile
(``_halo_block_rows``), so a tile reads ``block_rows + 2*hb`` rows of the
field instead of three whole tiles; where no such ``hb`` exists (a tile of
8 rows, or one that is not a multiple of 8) the neighbour blocks are whole
tiles. Block shape comes from the shared VMEM budget planner
(``repro.ir.plan``), which still counts three whole tiles per haloed field.

Multi-field programs get N input refs, one per declared field, each with a
halo sized by THAT field's composed radius (``field_radii``): the
evolving state carries the full chain radius, a destaggered velocity its own
smaller reach, and a radius-0 coefficient field streams exactly one block
per tile with no neighbour fetches at all. Shallower-halo fields are
zero-padded up to the common state grid inside the kernel — pad rows are
never read into a kept output point, which is what keeps the padding free.

Temporal blocking is first-class: a composed program (``repeat(p, k)``)
loads its tile ONCE with a depth-``k*r`` halo and applies the chain's k
sweeps while the data stays VMEM-resident, re-applying the global boundary
ring between sweeps with ABSOLUTE row indices (``slab_sweep``) so the k-step
kernel bit-matches k full-shape applications. Compulsory HBM traffic per
simulated step drops ~k-fold — the generalisation of the hard-coded
two-step trick that ``kernels/hdiff/multistep.py`` now wraps.

The absolute indexing takes a traced ``(row_offset, rows_global,
col_offset, cols_global)`` tuple through SMEM, so the same kernel runs
standalone (offsets 0) and inside a ``shard_map`` shard (offsets from
``axis_index``; see ``lower_sharded``) — including column slabs of a 2-D
rows x cols domain decomposition, where the global column ring is applied
by absolute index exactly like rows.

1-D programs (jacobi1d) lower to a kernel over blocks of whole rows (a
multiple of 8 of them, the TPU sublane rule) with the column halo handled
in-tile, mirroring ``kernels.stencil2d.jacobi1d_pallas``.

Column embeds are lane-axis concatenations (``embed_cols``), never
indexed-update scatters, which Mosaic cannot lower.
"""

from __future__ import annotations

import functools
import re
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.ir.evaluate import (
    embed_cols,
    interior_eval,
    resolve_field_arrays,
    ring_crop,
    slab_sweep,
)
from repro.ir.graph import StencilProgram
from repro.ir.plan import SUBLANES, grid_block_rows, pick_block_rows, resident_blocks
from repro.obs import metrics
from repro.runtime import pallas_interpret

Array = jax.Array


def _kernel_name(program: StencilProgram) -> str:
    """The ``pallas_call`` name of ``program``'s kernel, which names it in
    jaxprs, compiled HLO and device traces: ``ir_<program>`` made an
    identifier."""
    return re.sub(r"\W", "_", f"ir_{program.name}")


def _halo_block_rows(block_rows: int, hf: int) -> int:
    """Rows of the blocks that carry a field's ``hf``-row halo into a
    ``block_rows`` tile: the smallest multiple of 8 (the sublane tile) that
    covers ``hf`` and divides the tile, so the neighbour rows sit on block
    boundaries; ``block_rows`` itself (whole neighbour tiles) where no
    narrower block does."""
    for hb in range(SUBLANES, block_rows, SUBLANES):
        if hb >= hf and block_rows % hb == 0:
            return hb
    return block_rows


def _generic_kernel(
    *refs, program, block_rows, halo, col_sharded, field_halos,
):
    """Kernel body: blocks are (1, block_rows, C); grid is (depth, row_tiles).

    ``refs`` lays out, per input field in ``program.inputs`` order, a
    ``(prev, cur, next)`` triple when that field's halo is nonzero or a
    lone ``cur`` when it is zero, followed by ``meta_ref`` and
    one OUTPUT ref per evolving field (``program.outputs`` order — a
    coupled system writes all its updated fields from the one fused VMEM
    residency). ``field_halos[f]`` is the field's composed chain radius —
    every evolving field carries the program's full chain radius ``halo``
    (its ring rows must hold true values for the passthrough), every other
    field only the rows it is actually read at (a radius-0 coefficient
    fetches ONE block, no neighbours). ``prev`` and ``next`` are the
    blocks of at least ``hf`` rows that end just above and start just below
    ``cur`` (halo blocks, or whole tiles; ``_halo_block_rows``): the
    kernel keeps their last and first ``hf`` rows. Fields with a shallower
    halo are zero-padded up to the common ``halo`` grid — the pad rows are
    provably never read into a kept output point (reads reach at most the
    field's composed radius past the kept region).

    Each of the chain's sweeps shrinks the state slab by its own radius
    while re-applying the global radius-r ring at ABSOLUTE row indices
    (``meta_ref`` holds the traced ``(row_offset, rows_global, col_offset,
    cols_global)`` tuple — ``(0, rows, 0, cols)`` standalone, the shard's
    global placement under ``lower_sharded``); non-evolving fields feed
    every sweep through grid-aligned views (``slab_sweep`` extras).

    ``col_sharded`` (static) selects the column mode: False keeps the
    full-width sweep (columns never tiled — the array carries the whole
    global column extent, local column ring); True runs the column-slab
    sweep for 2-D domain decomposition — the array's outer ``halo`` columns
    are the shard's column halo, the sweep shrinks them away, and the
    result is re-embedded so the output block keeps the input width (the
    caller slices the stale halo columns off).
    """
    out_fields = tuple(program.outputs)
    n_out = len(out_fields)
    out_refs = refs[-n_out:]
    meta_ref = refs[-n_out - 1]
    i = pl.program_id(1)
    it = iter(refs[: -n_out - 1])
    slabs: dict[str, jax.Array] = {}
    state_curs: dict[str, jax.Array] = {}
    for f in program.inputs:
        hf = field_halos[f]
        if hf:
            prev_ref, cur_ref, next_ref = next(it), next(it), next(it)
            cur = cur_ref[0].astype(jnp.float32)
            x = jnp.concatenate(
                [
                    prev_ref[0, -hf:, :].astype(jnp.float32),
                    cur,
                    next_ref[0, :hf, :].astype(jnp.float32),
                ],
                axis=0,
            )  # (block_rows + 2*hf, C)
        else:
            cur = next(it)[0].astype(jnp.float32)
            x = cur
        if hf < halo:
            pad = jnp.zeros((halo - hf, x.shape[-1]), jnp.float32)
            x = jnp.concatenate([pad, x, pad], axis=0)
        slabs[f] = x
        if f in program.outputs:
            state_curs[f] = cur
    states = {f: slabs.pop(f) for f in out_fields}
    # Single-output programs sweep the bare state array (the legacy,
    # bit-tested path); coupled systems thread the {field: slab} dict.
    state = states[program.passthrough] if n_out == 1 else states
    extras = slabs or None
    base = meta_ref[0, 0] + i * block_rows - halo  # global id of states' first row
    if not col_sharded or halo == 0:
        vals = slab_sweep(program, state, base, meta_ref[0, 1], extras=extras)
        if n_out == 1:
            vals = {program.passthrough: vals}
        for f, out_ref in zip(out_fields, out_refs):
            out_ref[0] = vals[f].astype(out_ref.dtype)
        return
    vals = slab_sweep(
        program, state, base, meta_ref[0, 1], meta_ref[0, 2], meta_ref[0, 3],
        extras=extras,
    )  # (block_rows, C - 2*halo) per output
    if n_out == 1:
        vals = {program.passthrough: vals}
    for f, out_ref in zip(out_fields, out_refs):
        out_ref[0] = embed_cols(state_curs[f], vals[f], halo).astype(out_ref.dtype)


def _kernel_1d(x_ref, out_ref, *, program):
    x = x_ref[...].astype(jnp.float32)
    for prog in program.chain:
        vals = ring_crop(prog, interior_eval(prog, {prog.inputs[0]: x}))
        x = embed_cols(x, vals, prog.radius)
    out_ref[...] = x.astype(out_ref.dtype)


def lower_pallas(
    program: StencilProgram,
    *,
    block_rows: int | None = None,
    vmem_budget: int | None = None,
    interpret: bool | None = None,
) -> Callable[[Array | Mapping[str, Array]], Array]:
    """Builds ``x -> program(x)`` as a fused Pallas kernel.

    For a composed program (``program.steps > 1``) the kernel applies all k
    sweeps per VMEM residency — one HBM round-trip per k simulated steps.

    Args:
      program: a 2-D IR program (scalars baked into the graph). Multi-field
        programs are first-class: pass a ``{field: array}`` mapping (all
        arrays the same shape); the kernel takes one input ref per field
        with a per-field halo sized by that field's composed radius
        (``field_radii``), so a radius-0 coefficient field streams exactly
        one block per tile and no neighbour blocks.
      block_rows: VMEM row-tile override, which must divide the rows;
        default picks the largest aligned divisor of rows fitting the shared
        VMEM budget (>= the inferred chain halo), or, when no aligned
        divisor fits, a fitting aligned tile whose ``cdiv`` grid leaves the
        last tile ragged (``repro.ir.plan.grid_block_rows``).
      vmem_budget: VMEM byte budget for the kernel's pipelined blocks
        (arg > env > 4 MiB; see ``repro.ir.plan``).
      interpret: force interpreter mode; default = interpret iff not on TPU
        (``repro.runtime.pallas_interpret``; refused on a TPU backend).

    The returned function also accepts keyword-only ``row_offset`` /
    ``rows_global`` (possibly traced) so ``lower_sharded`` can run the same
    kernel on a halo-padded shard block with true global row indices, and
    ``col_offset`` / ``cols_global`` for 2-D (rows x cols) decomposition:
    passing ``cols_global`` marks the arrays as column slabs whose outer
    chain-radius columns are halo (the sweep consumes them and the global
    column ring is applied by absolute index, mirroring rows).
    """
    if program.ndim == 1:
        if len(program.inputs) != 1:
            raise ValueError(
                "1-D pallas lowering supports single-input programs only, "
                f"got {program.inputs}"
            )
        return _lower_pallas_1d(program, interpret=interpret)
    if program.ndim != 2:
        raise ValueError(f"unsupported ndim {program.ndim}")

    fields = program.inputs
    halo = program.radius  # full chain radius: k*r for repeat(p, k)
    # Shared per-field halo rule (state at full chain radius, other fields
    # at their own composed radius) — same home as the sharded exchange
    # and the wire-byte models.
    field_halos = program.exchange_radii()
    min_block = max(halo, 1)
    n_haloed = sum(1 for f in fields if field_halos[f])
    resident = resident_blocks(
        n_haloed, len(fields) - n_haloed, len(program.outputs)
    )
    kernel_name = _kernel_name(program)

    @functools.partial(
        jax.jit, static_argnames=("br", "interp", "col_sharded")
    )
    def _call(arrays, row_offset, rows_global, col_offset, cols_global, br,
              interp, col_sharded):
        depth, rows, cols = arrays[0].shape
        # When ``br`` does not divide the rows (``grid_block_rows``) the last
        # tile overhangs them: Pallas leaves its out-of-bounds reads
        # unspecified and drops its out-of-bounds writes. No overhang value
        # reaches a kept point. An interior point reads at most the chain
        # radius away, which stays inside the real rows; the bottom ring rows
        # pass through by absolute index (``rows_global``), selected with
        # ``jnp.where``, so even a NaN read past the end never blends in.
        row_tiles = pl.cdiv(rows, br)
        meta = jnp.stack(
            [
                jnp.asarray(row_offset, jnp.int32),
                jnp.asarray(rows_global, jnp.int32),
                jnp.asarray(col_offset, jnp.int32),
                jnp.asarray(cols_global, jnp.int32),
            ]
        ).reshape(1, 4)
        kernel = functools.partial(
            _generic_kernel,
            program=program,
            block_rows=br,
            halo=halo,
            col_sharded=col_sharded,
            field_halos=field_halos,
        )
        spec = lambda fn: pl.BlockSpec((1, br, cols), fn)  # noqa: E731

        def halo_specs(hb):
            # ``prev`` ends at the tile's first row and ``next`` starts after
            # its last, in units of ``hb`` rows (``hb`` divides ``br``). At
            # the grid's edges and past the ragged last tile the clamped
            # blocks feed only ring rows, as above.
            per_tile, last = br // hb, pl.cdiv(rows, hb) - 1
            band = lambda fn: pl.BlockSpec((1, hb, cols), fn)  # noqa: E731
            return [
                band(lambda d, i: (d, jnp.maximum(i * per_tile - 1, 0), 0)),
                spec(lambda d, i: (d, i, 0)),
                band(lambda d, i: (d, jnp.minimum((i + 1) * per_tile, last), 0)),
            ]

        in_specs = []
        operands = []
        for f, x in zip(fields, arrays):
            if field_halos[f]:
                in_specs += halo_specs(_halo_block_rows(br, field_halos[f]))
                operands += [x, x, x]
            else:
                in_specs.append(spec(lambda d, i: (d, i, 0)))
                operands.append(x)
        in_specs.append(
            pl.BlockSpec(
                (1, 4), lambda d, i: (0, 0), memory_space=pltpu.MemorySpace.SMEM
            )
        )
        out_fields = tuple(program.outputs)
        if len(out_fields) == 1:
            state = arrays[fields.index(program.passthrough)]
            outs = [pl.pallas_call(
                kernel,
                grid=(depth, row_tiles),
                in_specs=in_specs,
                out_specs=spec(lambda d, i: (d, i, 0)),
                out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
                interpret=interp,
                name=kernel_name,
            )(*operands, meta)]
        else:
            # Coupled systems: one fused kernel writes every evolving field's
            # updated block — N output refs, one VMEM residency.
            outs = pl.pallas_call(
                kernel,
                grid=(depth, row_tiles),
                in_specs=in_specs,
                out_specs=[spec(lambda d, i: (d, i, 0)) for _ in out_fields],
                out_shape=[
                    jax.ShapeDtypeStruct(
                        arrays[fields.index(f)].shape, arrays[fields.index(f)].dtype
                    )
                    for f in out_fields
                ],
                interpret=interp,
                name=kernel_name,
            )(*operands, meta)
        return outs[0] if len(out_fields) == 1 else dict(zip(out_fields, outs))

    def fn(x: Array | Mapping[str, Array], *, row_offset=0, rows_global=None,
           col_offset=0, cols_global=None) -> Array:
        arrays = resolve_field_arrays(program, x, ndim=3)
        _, rows, cols = arrays[0].shape
        br = block_rows
        if br is None:
            br = grid_block_rows(
                rows, cols, resident=resident, itemsize=arrays[0].dtype.itemsize,
                budget_bytes=vmem_budget, min_rows=min(min_block, rows),
            )
            if rows % br:
                # Host builds and traces of a ragged grid, not device calls.
                metrics.inc("ir.lower_pallas.ragged_builds")
        elif rows % br:
            raise ValueError(f"rows={rows} not divisible by block_rows={br}")
        if br < min_block:
            raise ValueError(
                f"block_rows={br} < inferred row halo {min_block} for "
                f"program {program.name!r}"
            )
        hfs = [field_halos[f] for f in fields if field_halos[f]]
        if any(_halo_block_rows(br, hf) < br for hf in hfs):
            # Host builds and traces whose neighbour blocks are halo blocks.
            metrics.inc("ir.lower_pallas.halo_block_builds")
        interp = pallas_interpret(interpret)
        if rows_global is None:
            rows_global = rows
        # cols_global given => the arrays are column slabs of a wider grid
        # (2-D domain decomposition): static mode switch for the kernel.
        col_sharded = cols_global is not None
        if cols_global is None:
            cols_global = cols
        return _call(
            arrays, row_offset, rows_global, col_offset, cols_global, br, interp,
            col_sharded,
        )

    # Per-call timer/counter under the repro.obs registry (no-op when
    # disabled; steps aside when traced inside lower_sharded's shard_map).
    return metrics.instrument_call(fn, f"ir.lower_pallas.{program.name}")


def _lower_pallas_1d(program, *, interpret):
    kernel_name = _kernel_name(program)

    @functools.partial(jax.jit, static_argnames=("interp",))
    def _call(x, interp):
        batch, n = x.shape
        # Whole rows per block, a multiple of 8 of them (or the whole batch):
        # the TPU's (8, 128) block rule. Input and output, double-buffered.
        bb = pick_block_rows(
            batch, n, itemsize=x.dtype.itemsize, resident=resident_blocks(0, 1, 1)
        )
        kernel = functools.partial(_kernel_1d, program=program)
        return pl.pallas_call(
            kernel,
            grid=(batch // bb,),
            in_specs=[pl.BlockSpec((bb, n), lambda b: (b, 0))],
            out_specs=pl.BlockSpec((bb, n), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interp,
            name=kernel_name,
        )(x)

    def fn(x: Array) -> Array:
        if x.ndim != 2:
            raise ValueError(f"expected (batch, n), got shape {x.shape}")
        return _call(x, pallas_interpret(interpret))

    return metrics.instrument_call(fn, f"ir.lower_pallas.{program.name}")
