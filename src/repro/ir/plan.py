"""VMEM tile planning shared by the hand-written kernels and the IR lowerer,
plus the 2-D mesh-factorization planner for ``lower_sharded``.

The budget is the VMEM the Pallas grid pipeline's blocks may hold in all:
three slabs (prev / cur / next) per haloed input field, one block per
halo-free input and per output, each double-buffered (the shimDMA
ping-pong of §3.2.1) — :func:`resident_blocks` counts them, and
:func:`pick_block_rows` sizes the tile so that many blocks fit
(:func:`grid_block_rows` gives a grid whose only aligned tile is too big a
``cdiv`` grid of fitting tiles, the last one ragged). The rest
of the chip's scoped VMEM is left to the kernel body's temporaries. The
budget defaults to 4 MiB and is configurable per call (``budget_bytes``)
or process-wide via the ``REPRO_VMEM_BUDGET`` environment variable
(bytes).

:func:`plan_partition` is the SPARTA §3.4 placement question for the 2-D
decomposition: given a device count, which rows x cols factorization
balances the workload at the least wire traffic? It enumerates the feasible
factorizations and minimizes the exact 2-axis ``halo_exchange_bytes`` model
(the one ``benchmarks/fig10_scaling.py`` verifies against measured HLO
collective bytes).
"""

from __future__ import annotations

import dataclasses
import os

DEFAULT_VMEM_TILE_BUDGET = 4 * 1024 * 1024
# A block's second-to-last dim must be a multiple of the TPU's 8 sublanes
# or span the whole array dim (the (8, 128) block rule).
SUBLANES = 8
VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET"


def vmem_tile_budget(budget_bytes: int | None = None) -> int:
    """Resolves the pipeline's VMEM budget: explicit arg > env var > 4 MiB.

    A non-positive budget is a configuration error, not a request for
    1-row tiles — it raises instead of silently degrading every kernel."""
    if budget_bytes is not None:
        budget, source = int(budget_bytes), "budget_bytes"
    else:
        env = os.environ.get(VMEM_BUDGET_ENV)
        if not env:
            return DEFAULT_VMEM_TILE_BUDGET
        try:
            budget = int(env)
        except ValueError as e:
            raise ValueError(
                f"{VMEM_BUDGET_ENV} must be an integer byte count, got {env!r}"
            ) from e
        source = VMEM_BUDGET_ENV
    if budget <= 0:
        raise ValueError(
            f"{source} must be a positive byte count, got {budget}"
        )
    return budget


def resident_blocks(n_haloed: int, n_plain: int, n_out: int) -> int:
    """Blocks a row-tiled Pallas kernel keeps in VMEM: three slabs per
    haloed input, one per halo-free input and one per output, all
    double-buffered by the grid pipeline."""
    return 2 * (3 * n_haloed + n_plain + n_out)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pick_block_rows(
    rows: int,
    cols: int,
    *,
    resident: int,
    itemsize: int = 4,
    budget_bytes: int | None = None,
    min_rows: int = 1,
) -> int:
    """Largest aligned divisor of ``rows`` whose ``resident`` (rows x cols)
    blocks fit the budget together.

    ``resident`` is the kernel's block count (:func:`resident_blocks`).
    Candidates are multiples of ``SUBLANES`` or ``rows`` itself (the TPU's
    8-sublane rule for the second-to-last block dim). ``min_rows`` is the
    kernel's structural floor (e.g. the three-slab halo trick needs
    ``block_rows >= halo``). If no candidate fits the budget, the smallest
    candidate >= ``min_rows`` is returned (correctness over budget; see
    :func:`grid_block_rows` for the remedy). If ``min_rows`` exceeds every
    divisor of ``rows`` (i.e. ``rows`` itself), no tiling can satisfy the
    kernel's floor — that raises rather than silently handing back an
    undersized tile.
    """
    budget = vmem_tile_budget(budget_bytes)
    if min_rows > rows:
        raise ValueError(
            f"min_rows={min_rows} exceeds every divisor of rows={rows}: the "
            f"grid is too shallow for this kernel's structural floor (halo)"
        )
    fallback = rows
    for cand in range(rows, 0, -1):
        if rows % cand or cand < min_rows or (cand % SUBLANES and cand != rows):
            continue
        fallback = cand
        if resident * cand * cols * itemsize <= budget:
            return cand
    return fallback


def grid_block_rows(
    rows: int,
    cols: int,
    *,
    resident: int,
    itemsize: int = 4,
    budget_bytes: int | None = None,
    min_rows: int = 1,
) -> int:
    """Row tile of a ``cdiv(rows, tile)`` grid whose blocks fit the budget.

    :func:`pick_block_rows`' answer when it fits; that tile divides the
    rows. Otherwise (typically a halo-padded grid, ``rows + 2 * halo``, that
    no multiple of 8 divides, whose only aligned tile is the whole dim) the
    rows are split into the fewest tiles no taller than the tallest aligned
    tile that fits, and the tile is their even share rounded up to a
    multiple of ``SUBLANES`` (and to ``min_rows``), never more than
    ``rows``. The grid's last tile then overhangs the rows."""
    budget = vmem_tile_budget(budget_bytes)
    per_row = resident * cols * itemsize
    own = pick_block_rows(
        rows, cols, resident=resident, itemsize=itemsize,
        budget_bytes=budget_bytes, min_rows=min_rows,
    )
    if per_row * own <= budget:
        return own
    fit = max(
        budget // per_row // SUBLANES * SUBLANES,
        _round_up(max(min_rows, 1), SUBLANES),
    )
    tiles = -(-rows // fit)
    return min(_round_up(max(-(-rows // tiles), min_rows), SUBLANES), rows)


# ---------------------------------------------------------------------------
# 2-D (rows x cols) mesh factorization for lower_sharded.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Partition2D:
    """A rows x cols shard factorization chosen by :func:`plan_partition`.

    ``wire_bytes`` is the whole-mesh traffic of ONE halo-exchange round
    under the exact 2-axis model (row bands + col bands + diagonal
    corners), summed per input field for multi-field programs; ``halo`` is
    the deepest exchanged band (the program's full chain radius — k*r for
    ``repeat(p, k)``, one round per k sweeps)."""

    row_shards: int
    col_shards: int
    halo: int
    wire_bytes: int

    @property
    def mesh_shape(self) -> tuple[int, int]:
        """Directly usable as ``lower_sharded(..., mesh_shape=...)``."""
        return (self.row_shards, self.col_shards)


def plan_partition(
    program,
    depth: int,
    rows: int,
    cols: int,
    n_devices: int,
    *,
    itemsize: int = 4,
) -> Partition2D:
    """Picks the rows x cols factorization of ``n_devices`` that minimizes
    the modeled wire bytes per exchange round for ``program`` on a
    (depth, rows, cols) grid.

    A factorization (R, C) is feasible when both grid dims divide evenly
    and each shard keeps at least the program's chain radius of rows/cols
    (the single-neighbour exchange floor ``lower_sharded`` enforces). Ties
    break toward fewer column shards (rows are the paper's native lane
    decomposition; columns are the contiguous/vectorised dim). The result
    never models more traffic than the 1-D row baseline (R=n, C=1) when
    that baseline is feasible — and covers meshes the 1-D baseline cannot
    reach at all (rows/n < halo), the remedy for the fine-mesh error.

    Distinct from ``repro.core.compound.plan_partition`` (depth x rows via
    the three-term roofline): this is the pure wire-traffic question for
    the 2-D spatial decomposition, answered with the byte model that
    ``fig10`` measures exactly.
    """
    # Lazy: repro.dist imports repro.core, which derives constants from
    # this package — importing it at module scope would be a cycle.
    from repro.dist.halo import program_halo_exchange_bytes

    halo = program.radius
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    best: Partition2D | None = None
    for r_sh in range(1, n_devices + 1):
        if n_devices % r_sh:
            continue
        c_sh = n_devices // r_sh
        if rows % r_sh or cols % c_sh:
            continue
        if halo > 0 and (
            (r_sh > 1 and rows // r_sh < halo) or (c_sh > 1 and cols // c_sh < halo)
        ):
            continue
        # Per-field wire sum: for single-input programs this is exactly the
        # old halo_exchange_bytes(halo=radius); multi-field programs add
        # each extra field's own (possibly zero) composed-radius traffic.
        wire = program_halo_exchange_bytes(
            program, depth, rows, cols, r_sh, itemsize=itemsize, col_shards=c_sh
        )
        cand = Partition2D(r_sh, c_sh, halo, wire)
        if (
            best is None
            or cand.wire_bytes < best.wire_bytes
            or (cand.wire_bytes == best.wire_bytes and c_sh < best.col_shards)
        ):
            best = cand
    if best is None:
        raise ValueError(
            f"no rows x cols factorization of {n_devices} devices fits grid "
            f"({rows}, {cols}) with halo {halo} (program {program.name!r}): "
            f"every split leaves a shard thinner than the halo band"
        )
    return best
