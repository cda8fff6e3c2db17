"""Domain-decomposed hdiff: depth-parallel planes + row halo exchange.

The paper's B-block scale-out (§3.4, Fig. 10) maps each depth plane to its
own compute resource (embarrassingly parallel — depth never enters the
stencil) and, past 64 shards, decomposes rows with a radius-2 halo. The
TPU analogue here is a ``shard_map`` over the device mesh:

  * ``depth_axis``: the (D, R, C) grid's depth dim is split over a mesh
    axis with ZERO collectives per step.
  * ``row_axis``: rows are split; each step every shard pushes its edge
    rows (HALO=2 of them — flux-of-Laplacian radius) to both neighbours
    with ``ppermute``, computes the stencil on the padded block, and keeps
    the rows it owns.

Global-boundary correctness uses ABSOLUTE row indexing: a shard knows its
row offset from ``axis_index``, so the radius-2 passthrough ring of the
global grid is preserved exactly, even when it falls entirely inside the
first/last shard — the zero halos ppermute delivers at the grid edges are
never read into an owned output row.

Columns decompose too (:func:`exchange_halos_2d`): the 2-D rows x cols
exchange adds a column band ppermute pair and four single-hop *diagonal*
corner ppermutes over the flattened ``(row_axis, col_axis)`` mesh pair —
the paper's 2-D AIE-array neighbour pattern. An axis with a single shard
skips its permutes entirely (zero pad, zero wire bytes).

Per-step wire traffic matches :func:`halo_exchange_bytes`, the analytical
model benchmarked by ``benchmarks/fig10_scaling.py``.
"""

from __future__ import annotations

from typing import Callable, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.hdiff import HALO, _hdiff_interior, hdiff, hdiff_simple
from repro.dist.hlo import parse_collective_bytes


def mesh_sizes(mesh) -> dict[str, int]:
    """``{axis name: size}`` in the mesh's declaration order.

    Duck-typed: ``axis_names`` + ``axis_sizes`` (a concrete ``Mesh`` or an
    ``AbstractMesh``, which has no ``devices``), falling back to
    ``devices.shape``."""
    names = tuple(mesh.axis_names)
    sizes = getattr(mesh, "axis_sizes", None)
    if sizes is None:
        sizes = mesh.devices.shape
    return dict(zip(names, tuple(sizes)))


def _check_band(extent: int, halo: int, what: str) -> None:
    """The single-neighbour ppermute sources each halo band from the
    IMMEDIATE neighbour only: a shard owning fewer than ``halo`` rows/cols
    cannot provide a full band — the slices would silently shorten and
    interiors would compute from the wrong data, so raise instead."""
    if extent < halo:
        raise ValueError(
            f"{what}/shard {extent} < halo {halo}: the single-neighbour "
            f"ppermute exchange cannot deliver a depth-{halo} halo band; "
            f"use fewer {what} shards, shard the other grid axis instead, "
            f"or use a smaller halo / fewer fused steps"
        )


def _band_halos(block: jax.Array, axis_name, n_shards: int, halo: int, dim: int):
    """(lo_halo, hi_halo) bands of width ``halo`` along ``dim`` (-2 rows or
    -1 cols), fetched from the two axis neighbours. With a single shard the
    permutes are SKIPPED entirely — both halos are explicit ``halo``-wide
    zero pads (the axis has no neighbours to source from, so even an extent
    thinner than ``halo`` is fine), matching the zeros ``ppermute`` delivers
    at uncovered grid edges but costing zero collective bytes
    (regression-tested via ``parse_collective_bytes``)."""
    if n_shards == 1:
        shape = list(block.shape)
        shape[dim] = halo
        z = jnp.zeros(tuple(shape), block.dtype)
        return z, z
    _check_band(block.shape[dim], halo, "rows" if dim == -2 else "cols")
    lo_src = block[..., -halo:, :] if dim == -2 else block[..., :, -halo:]
    hi_src = block[..., :halo, :] if dim == -2 else block[..., :, :halo]
    fwd = [(j, j + 1) for j in range(n_shards - 1)]  # my hi band -> next's lo halo
    bwd = [(j + 1, j) for j in range(n_shards - 1)]  # my lo band -> prev's hi halo
    return (
        jax.lax.ppermute(lo_src, axis_name, fwd),
        jax.lax.ppermute(hi_src, axis_name, bwd),
    )


def exchange_row_halos(block: jax.Array, row_axis: str, n_shards: int, halo: int = HALO):
    """Pads ``block`` (..., R_loc, C) with ``halo`` rows from each row
    neighbour via two ``ppermute`` pushes. Edge shards receive zeros on
    their outward side (ppermute's fill for uncovered targets); callers
    must not emit output rows computed from them (see absolute-row mask).
    With a single row shard the permutes are skipped (pure zero padding,
    zero collective bytes). Returns (..., R_loc + 2*halo, C).

    Sharded axes require ``R_loc >= halo`` (see :func:`_check_band`;
    regression-tested in tests/multidev/_ir_check.py)."""
    top_halo, bot_halo = _band_halos(block, row_axis, n_shards, halo, dim=-2)
    return jnp.concatenate([top_halo, block, bot_halo], axis=-2)


def exchange_halos_2d(
    block: jax.Array,
    row_axis,
    col_axis,
    n_row: int,
    n_col: int,
    halo: int = HALO,
    *,
    mesh_axis_names=None,
):
    """2-D halo exchange: pads ``block`` (..., R_loc, C_loc) with ``halo``
    rows, cols, AND corners from its 8 mesh neighbours. Returns
    (..., R_loc + 2*halo, C_loc + 2*halo).

    Three permute families, each skipped when its axis has 1 shard (a
    1-shard axis gets explicit zero pads and may even be thinner than the
    halo — only SHARDED axes must satisfy the extent >= halo band-sourcing
    floor, matching ``plan_partition``'s feasibility rule):

      * row bands  — 2 ppermutes along ``row_axis`` (halo x C_loc each);
      * col bands  — 2 ppermutes along ``col_axis`` (R_loc x halo each);
      * corners    — 4 ppermutes of halo x halo patches routed DIAGONALLY in
        one hop over the flattened (row_axis, col_axis) axis pair
        (source/target pairs enumerate internal mesh vertices only), so the
        wire model stays symmetric under (rows, cols) transpose and grid-edge
        shards send nothing. ``jax.lax.ppermute`` numbers the flattened pair
        indices in the MESH's axis declaration order (not the tuple order
        passed), so ``mesh_axis_names`` — the full ordered axis-name tuple of
        the enclosing mesh — is REQUIRED whenever both axes are sharded; a
        wrong assumption here silently corrupts the corner points.

    Edge shards receive zeros on outward sides (ppermute's fill), exactly as
    in the 1-D exchange; the absolute row/col ring passthrough guarantees
    they are never read into an owned output point.
    """
    top, bot = _band_halos(block, row_axis, n_row, halo, dim=-2)
    left, right = _band_halos(block, col_axis, n_col, halo, dim=-1)

    h = halo
    if n_row > 1 and n_col > 1:
        if mesh_axis_names is None:
            raise ValueError(
                "exchange_halos_2d needs mesh_axis_names (the mesh's ordered "
                "axis-name tuple) when both grid axes are sharded: the "
                "diagonal corner ppermute numbers shards in mesh declaration "
                "order, and guessing it wrong corrupts corners silently"
            )
        order = [a for a in mesh_axis_names if a in (row_axis, col_axis)]
        if order != [row_axis, col_axis] and order != [col_axis, row_axis]:
            raise ValueError(
                f"mesh axes {tuple(mesh_axis_names)} do not contain exactly "
                f"{row_axis!r} and {col_axis!r}"
            )
        row_major = order[0] == row_axis
        # Flatten (row i, col j) the way ppermute numbers the axis pair:
        # leading declared axis varies slowest.
        axes = (row_axis, col_axis) if row_major else (col_axis, row_axis)
        if row_major:
            flat = lambda i, j: i * n_col + j  # noqa: E731
        else:
            flat = lambda i, j: j * n_row + i  # noqa: E731

        def corner(src, pairs):
            return jax.lax.ppermute(src, axes, pairs)

        rng_i, rng_j = range(n_row - 1), range(n_col - 1)
        # My top-left halo corner = (i-1, j-1)'s bottom-right block corner, etc.
        tl = corner(block[..., -h:, -h:],
                    [(flat(i, j), flat(i + 1, j + 1)) for i in rng_i for j in rng_j])
        tr = corner(block[..., -h:, :h],
                    [(flat(i, j + 1), flat(i + 1, j)) for i in rng_i for j in rng_j])
        bl = corner(block[..., :h, -h:],
                    [(flat(i + 1, j), flat(i, j + 1)) for i in rng_i for j in rng_j])
        br = corner(block[..., :h, :h],
                    [(flat(i + 1, j + 1), flat(i, j)) for i in rng_i for j in rng_j])
    else:
        # A 1-shard axis has no diagonal neighbours: corners are grid-edge
        # pads on at least one side, i.e. zeros — no wire bytes.
        zc = jnp.zeros(block.shape[:-2] + (h, h), block.dtype)
        tl = tr = bl = br = zc

    left_col = jnp.concatenate([tl, left, bl], axis=-2)
    right_col = jnp.concatenate([tr, right, br], axis=-2)
    mid = jnp.concatenate([top, block, bot], axis=-2)
    return jnp.concatenate([left_col, mid, right_col], axis=-1)


def owned_rows_mask(shard_index, rows_local: int, rows_global: int, halo: int = HALO):
    """Boolean (rows_local,): which of my rows are GLOBAL interior rows
    (the radius-``halo`` global boundary ring passes through)."""
    g = shard_index * rows_local + jnp.arange(rows_local)
    return (g >= halo) & (g < rows_global - halo)


def halo_exchange_bytes(
    depth: int,
    rows: int,
    cols: int,
    row_shards: int,
    itemsize: int = 4,
    halo: int = HALO,
    steps: int = 1,
    col_shards: int = 1,
) -> int:
    """Total bytes on the wire for ONE halo-exchange round, summed over the
    whole mesh. Independent of depth sharding (depth planes are disjoint;
    the per-device blocks are smaller but more numerous).

    2-axis model (matches :func:`exchange_halos_2d` exactly; ``h`` is the
    exchanged band depth ``halo * steps``):

      * row bands:  every internal row boundary moves ``h`` full-width rows
        each direction — ``2 (R-1) * depth * h * cols`` elements (the
        per-strip width is ``cols / C`` but there are ``C`` strips);
      * col bands:  symmetrically ``2 (C-1) * depth * h * rows``;
      * corners:    4 diagonal ``h x h`` patches across each of the
        ``(R-1)(C-1)`` internal mesh vertices — ``4 (R-1)(C-1) * depth *
        h^2``. Quadratic in ``h``: deep temporal-blocked halos pay a
        growing (but tiny) corner tax.

    The model is symmetric under (rows, R) <-> (cols, C) transpose, and
    ``col_shards=1`` reduces exactly to the 1-D row formula.

    ``steps`` models temporal blocking (``repeat(p, steps)`` lowered via
    ``lower_sharded``): the exchanged band deepens to ``steps * halo`` but
    one round serves ``steps`` fused sweeps, so exchange ROUNDS — the
    latency term — per simulated step drop ``steps``-fold. Divide by
    ``steps`` for per-step bytes."""
    h = halo * steps
    total = 0
    if row_shards > 1:
        total += 2 * (row_shards - 1) * depth * h * cols
    if col_shards > 1:
        total += 2 * (col_shards - 1) * depth * h * rows
    if row_shards > 1 and col_shards > 1:
        total += 4 * (row_shards - 1) * (col_shards - 1) * depth * h * h
    return total * itemsize


def halo_exchange_bytes_per_shard(
    local_depth: int,
    local_rows: int,
    local_cols: int,
    itemsize: int = 4,
    halo: int = HALO,
    steps: int = 1,
    row_sharded: bool = True,
    col_sharded: bool = False,
) -> int:
    """Per-chip collective-permute RESULT bytes for one exchange round — what
    ``parse_collective_bytes`` measures on the compiled SPMD program (every
    chip executes the same permutes; an interior chip receives them all).

    Row bands 2 x (D_loc, h, C_loc), col bands 2 x (D_loc, R_loc, h), and
    4 diagonal corners (D_loc, h, h) when both axes are sharded."""
    h = halo * steps
    total = 0
    if row_sharded:
        total += 2 * local_depth * h * local_cols
    if col_sharded:
        total += 2 * local_depth * local_rows * h
    if row_sharded and col_sharded:
        total += 4 * local_depth * h * h
    return total * itemsize


def program_exchange_radii(program) -> dict[str, int]:
    """Per-field EXCHANGED halo depth: delegates to
    :meth:`repro.ir.graph.StencilProgram.exchange_radii`, the one home of
    the rule, so the byte models here, ``lower_sharded``'s exchange and
    ``lower_pallas``'s in-tile halos can never drift apart."""
    return program.exchange_radii()


def program_halo_exchange_bytes(
    program,
    depth: int,
    rows: int,
    cols: int,
    row_shards: int,
    itemsize: int = 4,
    col_shards: int = 1,
) -> int:
    """Whole-mesh wire bytes for ONE exchange round of a (possibly
    multi-field, possibly temporally-composed) IR program: the per-field
    sum of :func:`halo_exchange_bytes`.

    The evolving (:attr:`~repro.ir.graph.StencilProgram.passthrough`) field
    exchanges the program's full chain radius; every other input exchanges
    its own composed access radius (``field_radii``), so a radius-0
    coefficient field contributes ZERO bytes. Temporal blocking is already
    baked into the composed radii (``repeat(p, k)``'s state radius is k*r),
    so no ``steps`` factor appears — one round still serves the whole
    chain. For a single-input program this reduces exactly to
    ``halo_exchange_bytes(..., halo=program.radius)``.

    Matches what ``repro.ir.lower_sharded`` puts on the wire exactly
    (measured per-chip in fig10/fig13 via ``parse_collective_bytes``).
    """
    return sum(
        halo_exchange_bytes(
            depth, rows, cols, row_shards,
            itemsize=itemsize, halo=r, col_shards=col_shards,
        )
        for r in program_exchange_radii(program).values()
    )


def program_halo_exchange_bytes_per_shard(
    program,
    local_depth: int,
    local_rows: int,
    local_cols: int,
    itemsize: int = 4,
    row_sharded: bool = True,
    col_sharded: bool = False,
) -> int:
    """Per-chip collective-permute RESULT bytes for one multi-field exchange
    round — the per-field sum of :func:`halo_exchange_bytes_per_shard`
    (what ``parse_collective_bytes`` measures on the compiled program)."""
    return sum(
        halo_exchange_bytes_per_shard(
            local_depth, local_rows, local_cols,
            itemsize=itemsize, halo=r,
            row_sharded=row_sharded, col_sharded=col_sharded,
        )
        for r in program_exchange_radii(program).values()
    )


def call_exchange_bytes(
    program,
    grid: tuple[int, int, int],
    shards: tuple[int, int, int],
    itemsize: int | Mapping[str, int] = 4,
) -> dict[str, int]:
    """Per-chip model bytes of the exchange ONE ``lower_sharded`` call of
    ``program`` issues, per exchanged field: ``{field: bytes}`` for a global
    ``(D, R, C)`` ``grid`` split into ``(depth, rows, cols)`` ``shards``.

    ``itemsize`` is one item size for every field or ``{field: size}``.
    Empty where the call exchanges nothing (a radius-0 program, or neither
    grid axis sharded); radius-0 fields are left out. Host arithmetic only,
    so ``lower_sharded``'s recorder and ``ForecastServer`` (whose calls are
    traced inside its batch call) count a call the same way."""
    n_depth, n_row, n_col = shards
    if n_row == 1 and n_col == 1:
        return {}
    d, r, c = grid
    return {
        f: halo_exchange_bytes_per_shard(
            d // n_depth, r // n_row, c // n_col,
            itemsize=itemsize[f] if isinstance(itemsize, Mapping) else itemsize,
            halo=hf, row_sharded=n_row > 1, col_sharded=n_col > 1,
        )
        for f, hf in program_exchange_radii(program).items()
        if hf
    }


def measured_collective_permute_bytes(step_fn, x) -> tuple[float, int]:
    """PER-CHIP collective-permute result bytes of ``step_fn`` compiled on
    input ``x`` — the *measured* side of the wire-model claims, parsed from
    the post-SPMD HLO (:func:`repro.dist.hlo.parse_collective_bytes`).
    Returns ``(bytes, permute_count)``. Compiles (does not execute) the
    step."""
    coll = parse_collective_bytes(jax.jit(step_fn).lower(x).compile().as_text())
    return (
        coll["bytes"].get("collective-permute", 0.0),
        int(coll["counts"].get("collective-permute", 0)),
    )


def wire_drift_report(
    program,
    step_fn,
    x,
    *,
    local_depth: int,
    local_rows: int,
    local_cols: int,
    row_sharded: bool = True,
    col_sharded: bool = False,
    tolerance: float | None = None,
    name: str = "halo.wire",
):
    """Measured-vs-model drift check for one sharded lowering: compiles
    ``step_fn`` on ``x``, parses the per-chip collective-permute bytes, and
    compares them against :func:`program_halo_exchange_bytes_per_shard`.

    Records through :func:`repro.obs.drift.check_drift` into the active
    metrics registry (counters ``<name>.measured_bytes`` /
    ``<name>.model_bytes``, gauge ``<name>.ratio``, counter
    ``<name>.drift_flags`` when out of tolerance) and returns the
    :class:`~repro.obs.drift.DriftResult`. This is the standing form of the
    fig10/fig13 "ratio=1.000" lines: any accounting drift between what
    ``lower_sharded`` puts on the wire and what the byte model predicts
    flags immediately, on every instrumented run.
    """
    from repro.obs import events
    from repro.obs.drift import DEFAULT_TOLERANCE, check_drift

    itemsize = next(iter(x.values())).dtype.itemsize if isinstance(x, dict) else x.dtype.itemsize
    measured, _count = measured_collective_permute_bytes(step_fn, x)
    model = program_halo_exchange_bytes_per_shard(
        program, local_depth, local_rows, local_cols,
        itemsize=itemsize, row_sharded=row_sharded, col_sharded=col_sharded,
    )
    tol = DEFAULT_TOLERANCE if tolerance is None else tolerance
    result = check_drift(name, measured, model, tol)
    # The full report (clean or not) goes to the flight recorder: one event
    # per wire measurement, so a long run's event log carries the standing
    # measured==model evidence alongside its health probes.
    events.record("drift.report", name=name, program=program.name,
                  measured=result.measured, model=result.model,
                  ratio=result.ratio, ok=result.ok)
    return result


def gradient_halo_exchange_bytes_per_shard(
    program,
    local_depth: int,
    rows: int,
    cols: int,
    *,
    mesh_shape: tuple[int, int],
    itemsize: int = 4,
) -> int:
    """Per-chip collective-permute bytes of one VALUE-AND-GRAD step of a
    sharded differentiable lowering (``build_backend(..., "sharded-*",
    differentiable=True)``) — the backward-pass extension of
    :func:`program_halo_exchange_bytes_per_shard`.

    ``rows`` / ``cols`` are GLOBAL grid extents; ``local_depth`` is
    per-chip as in the forward model (depth is never padded or exchanged).
    Every backward sweep runs on the UNPADDED shards — the adjoint and
    augmented-forward sweeps lower with ``boundary="zero"``, whose zero
    extension rides the same exchange round (no pad/crop collectives) — so
    the model is a pure sum of per-program exchange rounds at the primal
    shard extents, mirroring ``repro.ir.autodiff.make_vjp`` sweep by sweep
    with the same per-field ``exchange_radii()`` rule the forward model
    uses:

      * the primal forward: one full-chain round;
      * per sweep with caches: one round of the AUGMENTED forward
        (:func:`~repro.ir.autodiff.augmented_forward`) — the plain sweep's
        radii plus one full-radius band per ``c~`` cache slot (cache slots
        are OUTPUTS, and the shared ``exchange_radii()`` rule moves every
        evolving field at the chain radius);
      * per non-final sweep without caches (adjoint reads the primal state
        but nothing cached — product-of-inputs shapes): one plain per-sweep
        round;
      * per sweep: one round of the ADJOINT program — adjoint radii equal
        primal radii, so this mirrors the forward exchange exactly.

    Linear chains skip every state-recompute term (their adjoints never
    read the primal). Measured-vs-model is asserted at ratio 1.000 by
    ``tests/multidev/_grad_check.py`` and ``benchmarks/fig15_gradients.py``.
    """
    from repro.ir.autodiff import adjoint, augmented_forward, cache_fields

    n_row, n_col = int(mesh_shape[0]), int(mesh_shape[1])
    row_sh, col_sh = n_row > 1, n_col > 1
    r_loc, c_loc = rows // n_row, cols // n_col

    def one_round(p):
        return program_halo_exchange_bytes_per_shard(
            p, local_depth, r_loc, c_loc,
            itemsize=itemsize, row_sharded=row_sh, col_sharded=col_sh,
        )

    total = one_round(program)
    chain = program.chain
    needs_state = any(
        cache_fields(q)
        or any(r.field in q.inputs for op in adjoint(q).ops for r in op.reads)
        for q in chain
    )
    for i, q in enumerate(chain):
        if cache_fields(q):
            total += one_round(augmented_forward(q))
        elif needs_state and i < len(chain) - 1:
            total += one_round(q)
        total += one_round(adjoint(q))
    return total


def gradient_wire_drift_report(
    program,
    grad_step_fn,
    x,
    *,
    local_depth: int,
    rows: int,
    cols: int,
    mesh_shape: tuple[int, int],
    tolerance: float | None = None,
    name: str = "halo.grad_wire",
):
    """Measured-vs-model drift check for a sharded BACKWARD pass: compiles
    ``grad_step_fn`` (any pytree-in callable that returns the primal AND
    the cotangents — returning only gradients lets XLA dead-code the
    forward and undercounts) on ``x``, parses the per-chip
    collective-permute bytes, and compares against
    :func:`gradient_halo_exchange_bytes_per_shard`. Records through
    ``repro.obs.drift.check_drift`` exactly like :func:`wire_drift_report`
    (the standing "ratio=1.000" evidence, gradient edition)."""
    from repro.obs import events
    from repro.obs.drift import DEFAULT_TOLERANCE, check_drift

    leaves = jax.tree_util.tree_leaves(x)
    itemsize = leaves[0].dtype.itemsize
    measured, _count = measured_collective_permute_bytes(grad_step_fn, x)
    model = gradient_halo_exchange_bytes_per_shard(
        program, local_depth, rows, cols,
        mesh_shape=mesh_shape, itemsize=itemsize,
    )
    tol = DEFAULT_TOLERANCE if tolerance is None else tolerance
    result = check_drift(name, measured, model, tol)
    events.record("drift.report", name=name, program=program.name,
                  measured=result.measured, model=result.model,
                  ratio=result.ratio, ok=result.ok)
    return result


def make_sharded_hdiff(
    mesh,
    *,
    depth_axis: str | None = "data",
    row_axis: str | None = None,
    limit: bool = True,
    coeff: float = 0.025,
) -> Callable[[jax.Array], jax.Array]:
    """Builds a jitted ``psi (D, R, C) -> psi'`` matching single-device
    :func:`repro.core.hdiff` (or ``hdiff_simple`` with ``limit=False``)
    while domain-decomposed over ``mesh``.

    Args:
      mesh: the device mesh; axes named by ``depth_axis`` / ``row_axis``.
      depth_axis: mesh axis sharding dim 0 (planes), or None.
      row_axis: mesh axis sharding dim 1 (rows, with halo exchange), or
        None for pure depth parallelism.
      limit: apply the COSMO flux limiter (Eq. 2-3).
      coeff: scalar diffusion coefficient.
    """
    sizes = mesh_sizes(mesh)
    for ax in (depth_axis, row_axis):
        if ax is not None and ax not in sizes:
            raise ValueError(f"mesh {tuple(sizes)} has no axis {ax!r}")
    if depth_axis is not None and depth_axis == row_axis:
        raise ValueError("depth_axis and row_axis must be distinct mesh axes")
    n_row = sizes[row_axis] if row_axis is not None else 1
    n_depth = sizes[depth_axis] if depth_axis is not None else 1

    spec = P(depth_axis, row_axis if n_row > 1 else None, None)
    single = hdiff if limit else hdiff_simple

    def local_step(block: jax.Array) -> jax.Array:
        if row_axis is None or n_row == 1:
            # Full rows present locally: the single-device kernel's own
            # boundary handling is already correct.
            return single(block, coeff)
        padded = exchange_row_halos(block, row_axis, n_row)
        interior = _hdiff_interior(padded, coeff, limit=limit)  # rows: R_loc, cols: C-2H
        r_loc = block.shape[-2]
        mask = owned_rows_mask(jax.lax.axis_index(row_axis), r_loc, r_loc * n_row)
        cur = block[..., :, HALO:-HALO]
        out = jnp.where(mask[:, None], interior.astype(block.dtype), cur)
        return block.at[..., :, HALO:-HALO].set(out)

    mapped = jax.shard_map(
        local_step, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
    )

    @jax.jit
    def step(psi: jax.Array) -> jax.Array:
        if psi.ndim != 3:
            raise ValueError(f"expected (depth, rows, cols), got shape {psi.shape}")
        d, r, _ = psi.shape
        if n_depth > 1 and d % n_depth:
            raise ValueError(f"depth {d} not divisible by {n_depth} {depth_axis!r} shards")
        if n_row > 1:
            if r % n_row:
                raise ValueError(f"rows {r} not divisible by {n_row} {row_axis!r} shards")
            if r // n_row < HALO:
                raise ValueError(
                    f"rows/shard {r // n_row} < halo {HALO}: too many row shards"
                )
        return mapped(psi)

    return step
