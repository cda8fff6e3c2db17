"""Sharded lowering: shard_map + inferred-radius halo exchange per program.

The B-block scale-out of §3.4, driven entirely by the graph analysis: the
halo each shard pushes to its neighbours is the program's *inferred* chain
radius (k*r for a temporally-blocked ``repeat(p, k)``), not a hard-coded
constant, and the per-shard compute composes either the reference evaluator
or the fused Pallas kernel inside the shard — the ROADMAP's
"Pallas-kernel-inside-shard_map" item: VMEM-fused B-block residency *and*
domain decomposition in one step function.

Multi-field programs shard every declared input identically and exchange
halos PER FIELD at each field's composed radius (``field_radii``): the
evolving state moves the full chain radius, a velocity field its own reach,
and a radius-0 coefficient field moves NOTHING — zero wire bytes, which
``dist.halo.program_halo_exchange_bytes`` models exactly (measured-exact in
fig10/fig13). Exchanged aux fields are zero-padded up to the state's halo
grid so every field shares one coordinate system inside the shard; the pads
are never read into a kept output point.

Multi-OUTPUT programs (coupled systems — shallow-water's {u, v, h}) issue
ONE MERGED halo exchange covering all evolving fields per k sweeps: fields
needing the same band depth and dtype are stacked along a fresh leading
axis so each ppermute carries every field's band in a single message
(``merge_exchange=True``, the default) — same wire BYTES as per-field
exchanges (``program_halo_exchange_bytes`` sums the per-field terms either
way, still measured-exact) but one permute family instead of N, cutting the
per-round message count / latency term N-fold. ``merge_exchange=False``
keeps the sequential per-field exchanges (the comparison baseline
``benchmarks/fig13_multifield.py`` measures). The step returns ``{field:
array}`` with every output's updated full-shape state.

Domain decomposition is 2-D (rows x cols), like the paper's 2-D AIE array:
``row_axis`` and/or ``col_axis`` name mesh axes (or pass ``mesh_shape=(R,
C)`` to build a ("rows", "cols") mesh over the default devices), and
``dist.halo.exchange_halos_2d`` moves the row/col bands plus the four
diagonal corners. A grid too fine for row sharding (rows/shard < halo) can
therefore shard columns instead — the remedy the 1-D fine-mesh error now
points at.

``overlap=True`` splits every shard's work into interior compute — which
needs NO halo and is issued concurrently with the edge exchange, so XLA's
latency-hiding scheduler can run the ppermutes behind it — and the
radius-halo edge bands computed from the padded block afterwards. Both
pieces run the same ``slab_sweep`` slices over the same values (the edge
bands upcast to float32 when the inner is Pallas, mirroring the kernel),
so ``overlap=True`` bit-matches ``overlap=False`` — verified exactly on
the CPU/interpret test paths; on real TPU hardware the Mosaic-compiled
kernel and the XLA-fused edge bands may differ at the last ulp.

Temporal blocking amortises the wire: a composed program exchanges its
depth-``k*r`` halo ONCE per k fused sweeps, so halo-exchange *rounds* (the
latency term) per simulated step drop k-fold while the exchanged bytes per
round match ``halo_exchange_bytes(..., steps=k)`` exactly.

Global-boundary correctness uses absolute row AND column indexing, applied
PER SWEEP: every sweep of the chain re-applies the global boundary ring at
true global indices (``slab_sweep`` with the shard's row/col offsets), so
the zero halos ppermute delivers at the grid edges are never read into an
owned output point and the k-sweep result bit-matches k single-device
applications.

``repro.dist`` is imported lazily (it depends on ``repro.core``, which
derives its constants from this package).
"""

from __future__ import annotations

from typing import Callable, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.ir.evaluate import resolve_field_arrays, slab_sweep
from repro.ir.graph import StencilProgram
from repro.ir.lower_pallas import lower_pallas
from repro.ir.lower_reference import lower_reference
from repro.obs import events, metrics

Array = jax.Array

# Sentinel: distinguishes "caller did not pass depth_axis" (defaults to
# "data", or to None when mesh_shape builds the mesh) from an explicit one.
_DEPTH_DEFAULT = "__default_depth_axis__"


def lower_sharded(
    program: StencilProgram,
    mesh=None,
    *,
    depth_axis: str | None = _DEPTH_DEFAULT,
    row_axis: str | None = None,
    col_axis: str | None = None,
    mesh_shape: tuple[int, int] | None = None,
    overlap: bool = False,
    inner: str = "pallas",
    interpret: bool | None = None,
    vmem_budget: int | None = None,
    merge_exchange: bool = True,
    boundary: str = "ring",
) -> Callable[[Array], Array]:
    """Builds a jitted ``x (D, R, C) -> x'`` matching the single-device
    program application (all ``program.steps`` sweeps of it) while
    domain-decomposed over ``mesh``. Multi-output programs return
    ``{field: array}`` — one updated full-shape state per evolving field,
    exactly like the single-device lowerings.

    Args:
      program: 2-D IR program; a composed program fuses its k sweeps behind
        one depth-``k*r`` halo exchange.
      mesh: device mesh; axes named by ``depth_axis`` / ``row_axis`` /
        ``col_axis``. Mutually exclusive with ``mesh_shape``.
      depth_axis: mesh axis sharding dim 0 (planes, zero collectives), or None.
      row_axis: mesh axis sharding dim 1 (rows, halo exchange at the
        program's inferred chain radius), or None.
      col_axis: mesh axis sharding dim 2 (cols, symmetric halo exchange +
        diagonal corner traffic when rows are sharded too), or None.
      mesh_shape: ``(R, C)`` — build a rows x cols mesh over the first
        ``R * C`` default devices (axes named "rows"/"cols", no depth
        sharding) instead of passing ``mesh``; the factorization
        :func:`repro.ir.plan.plan_partition` picks.
      overlap: issue interior compute (halo-free) concurrently with the
        edge exchange, then fill the radius-halo edge bands — async
        halo/compute overlap. Bit-matches ``overlap=False``. The split
        activates only when the shard interior is non-empty (rows/shard >
        2*halo, and cols/shard > 2*halo when columns are sharded); thinner
        shards fall back to the serialized exchange-then-compute path
        (identical results, nothing left to overlap).
      inner: per-shard compute — "pallas" (fused VMEM kernel inside the
        shard) or "reference" (jnp evaluator). Under ``overlap=True`` the
        inner backend computes the interior; the thin edge bands always use
        the jnp evaluator.
      interpret / vmem_budget: forwarded to the Pallas lowering.
      merge_exchange: stack same-(radius, dtype) fields into ONE halo
        exchange per round (default) instead of one exchange per field —
        identical wire bytes, N-fold fewer permute messages for an N-field
        coupled system. Results are bit-identical either way (the stacked
        bands hold exactly the per-field bands).
      boundary: "ring" (default) applies the program's absolute-index
        global boundary ring — the forward semantics every lowering
        matches. "zero" instead computes EVERY owned point with ZERO
        extension beyond the global grid: the zero bands ``ppermute``
        already delivers at uncovered grid edges ARE the extension, so the
        mode costs exactly the same exchange round and no extra
        collectives. This is the evaluation the derived adjoint sweeps
        need (``repro.ir.autodiff``): cotangents exist at ring points too,
        and padding the sharded global grid instead would migrate shard
        boundaries — GSPMD inserts its own collective-permutes for that,
        polluting the measured-exact wire model. Single-sweep programs
        only.
    """
    from repro.dist.halo import (
        exchange_halos_2d,
        exchange_row_halos,
        mesh_sizes,
        program_exchange_radii,
    )

    if program.ndim != 2:
        raise ValueError("sharded lowering needs a 2-D program")
    if inner not in ("pallas", "reference"):
        raise ValueError(f"unknown inner backend {inner!r}")
    if boundary not in ("ring", "zero"):
        raise ValueError(f"unknown boundary mode {boundary!r}")
    if boundary == "zero" and program.steps != 1:
        raise ValueError(
            "boundary='zero' evaluates one merged DAG with zero extension; "
            "chains thread per-sweep rings — lower each chain entry "
            "separately (repro.ir.autodiff does)"
        )

    if mesh_shape is not None:
        if mesh is not None:
            raise ValueError("pass either mesh or mesh_shape, not both")
        if depth_axis != _DEPTH_DEFAULT or row_axis is not None or col_axis is not None:
            raise ValueError(
                "mesh_shape fixes the mesh axes to (rows, cols) with no depth "
                "sharding; don't pass depth_axis/row_axis/col_axis with it — "
                "build the mesh yourself to name axes"
            )
        from repro.launch.mesh import make_mesh

        r_sh, c_sh = mesh_shape
        mesh = make_mesh((int(r_sh), int(c_sh)), ("rows", "cols"))
        depth_axis, row_axis, col_axis = None, "rows", "cols"
    else:
        if mesh is None:
            raise ValueError("lower_sharded needs a mesh (or mesh_shape=(R, C))")
        if depth_axis == _DEPTH_DEFAULT:
            depth_axis = "data"

    sizes = mesh_sizes(mesh)
    axis_names = tuple(sizes)  # mesh declaration order (corner pair numbering)
    axes = {"depth_axis": depth_axis, "row_axis": row_axis, "col_axis": col_axis}
    for role, ax in axes.items():
        if ax is not None and ax not in sizes:
            raise ValueError(f"mesh {tuple(sizes)} has no axis {ax!r} ({role})")
    named = [ax for ax in axes.values() if ax is not None]
    if len(set(named)) != len(named):
        raise ValueError("depth_axis, row_axis and col_axis must be distinct mesh axes")
    n_row = sizes[row_axis] if row_axis is not None else 1
    n_col = sizes[col_axis] if col_axis is not None else 1
    n_depth = sizes[depth_axis] if depth_axis is not None else 1

    halo = program.radius  # full chain radius; exchanged once per k sweeps
    fields = program.inputs
    state_f = program.passthrough
    out_fields = tuple(program.outputs)
    n_out = len(out_fields)
    aux_fields = tuple(f for f in fields if f not in program.outputs)
    # Per-field exchanged halo (shared rule with the byte models): every
    # evolving field moves the full chain radius, every other field only
    # its own composed access radius — a radius-0 coefficient field is
    # exchanged NOT AT ALL (zero wire bytes for it, matching
    # dist.halo.program_halo_exchange_bytes exactly).
    fhalos = program_exchange_radii(program)

    if inner == "pallas":
        apply_full = lower_pallas(program, interpret=interpret, vmem_budget=vmem_budget)
    else:
        apply_full = lower_reference(program, mode="fused")

    spec = P(
        depth_axis,
        row_axis if n_row > 1 else None,
        col_axis if n_col > 1 else None,
    )

    def _as_dict(vals):
        """Normalises an inner-backend result to {output_field: array}."""
        return dict(vals) if isinstance(vals, Mapping) else {state_f: vals}

    def _full_input(states, aux):
        """The apply_full argument: bare array or field mapping."""
        if n_out == 1 and not aux_fields:
            return states[state_f]
        return {**aux, **states}

    def _offsets(block: Array):
        """Global index of the shard block's first row/col (pre-padding)."""
        r_loc, c_loc = block.shape[-2], block.shape[-1]
        off_r = jax.lax.axis_index(row_axis) * r_loc if n_row > 1 else 0
        off_c = jax.lax.axis_index(col_axis) * c_loc if n_col > 1 else 0
        return off_r, off_c, r_loc * n_row, c_loc * n_col

    def _exchange(a: Array, hf: int) -> Array:
        if n_col > 1:
            return exchange_halos_2d(
                a, row_axis, col_axis, n_row, n_col, hf,
                mesh_axis_names=axis_names,
            )
        return exchange_row_halos(a, row_axis, n_row, halo=hf)

    def _exchange_all(env):
        """One round of halo exchange for every field with a nonzero
        exchanged radius -> {field: exchanged block}.

        ``merge_exchange=True`` groups fields by (band depth, dtype) and
        stacks each group along a fresh leading axis, so ONE exchange (one
        ppermute per band/corner direction) carries every stacked field's
        band in a single message — the merged coupled-system exchange. The
        stacked bands are exactly the per-field bands, so unstacking
        reproduces the sequential exchanges bit-for-bit, and the wire BYTES
        are identical (``program_halo_exchange_bytes`` stays measured-exact
        under either mode)."""
        need = [f for f in fields if fhalos[f]]
        out = {}
        if not merge_exchange:
            for f in need:
                out[f] = _exchange(env[f], fhalos[f])
            return out
        groups: dict[tuple, list[str]] = {}
        for f in need:
            groups.setdefault((fhalos[f], env[f].dtype), []).append(f)
        for (hf, _dt), grp in groups.items():
            if len(grp) == 1:
                out[grp[0]] = _exchange(env[grp[0]], hf)
            else:
                stacked = _exchange(jnp.stack([env[f] for f in grp]), hf)
                for j, f in enumerate(grp):
                    out[f] = stacked[j]
        return out

    def _pad_to_halo(a: Array, hf: int) -> Array:
        """Zero-pads a radius-``hf``-exchanged block out to the state's
        ``halo`` grid so all fields stay aligned (rows always; cols too when
        columns are sharded). The zero pad is never read into a kept output
        point: reads reach at most ``hf`` past the kept region, which the
        exchange covered with true values."""
        pw = halo - hf
        if pw == 0:
            return a
        pad = [(0, 0)] * (a.ndim - 2)
        pad.append((pw, pw))
        pad.append((pw, pw) if n_col > 1 else (0, 0))
        return jnp.pad(a, pad)

    def _inner_padded(padded_states, padded_aux, off_r, off_c, r_glob, c_glob,
                      r_loc, c_loc):
        """Whole-shard compute on the halo-padded blocks ->
        {output field: (r_loc, c_loc) block}."""
        if inner == "pallas":
            if n_col > 1:
                vals = _as_dict(apply_full(
                    _full_input(padded_states, padded_aux),
                    row_offset=off_r - halo, rows_global=r_glob,
                    col_offset=off_c - halo, cols_global=c_glob,
                ))
                return {
                    f: v[..., halo : halo + r_loc, halo : halo + c_loc]
                    for f, v in vals.items()
                }
            vals = _as_dict(apply_full(
                _full_input(padded_states, padded_aux),
                row_offset=off_r - halo, rows_global=r_glob,
            ))
            return {f: v[..., halo : halo + r_loc, :] for f, v in vals.items()}
        extras = padded_aux or None
        state = padded_states[state_f] if n_out == 1 else padded_states
        if n_col > 1:
            vals = slab_sweep(program, state, off_r - halo, r_glob,
                              off_c - halo, c_glob, extras=extras)
        else:
            vals = slab_sweep(program, state, off_r - halo, r_glob, extras=extras)
        return _as_dict(vals)

    def _inner_interior(states, aux, off_r, off_c, r_glob, c_glob):
        """Halo-free interior compute on the UNPADDED blocks: output rows
        [halo, r_loc-halo) (and cols likewise when columns are sharded) —
        no data dependency on the exchange, so it can overlap it. Returns
        {output field: interior block}."""
        block = states[state_f]
        r_loc, c_loc = block.shape[-2], block.shape[-1]
        if inner == "pallas":
            if n_col > 1:
                vals = _as_dict(apply_full(
                    _full_input(states, aux),
                    row_offset=off_r, rows_global=r_glob,
                    col_offset=off_c, cols_global=c_glob,
                ))
                return {
                    f: v[..., halo : r_loc - halo, halo : c_loc - halo]
                    for f, v in vals.items()
                }
            vals = _as_dict(apply_full(
                _full_input(states, aux), row_offset=off_r, rows_global=r_glob
            ))
            return {f: v[..., halo : r_loc - halo, :] for f, v in vals.items()}
        extras = aux or None
        state = states[state_f] if n_out == 1 else states
        if n_col > 1:
            vals = slab_sweep(program, state, off_r, r_glob, off_c, c_glob,
                              extras=extras)
        else:
            vals = slab_sweep(program, state, off_r, r_glob, extras=extras)
        return _as_dict(vals)

    def _edge_bands(padded_states, padded_aux, off_r, off_c, r_glob, c_glob,
                    r_loc, c_loc):
        """The four radius-``halo`` edge bands of the shard's output, each
        one ``inner``-backend sweep over a static slice of the padded blocks
        (top/bottom span all owned cols; left/right cover the remaining
        interior rows). Aux fields ride the SAME slices — they live on the
        same padded grid, so one slicer keeps every field aligned. Each
        band is a {output field: block} dict."""
        h = halo

        def sweep(rows_sl, cols_sl, row0, col0):
            slabs = {
                f: a[..., rows_sl, cols_sl] for f, a in padded_states.items()
            }
            ex = {f: a[..., rows_sl, cols_sl] for f, a in padded_aux.items()}
            if inner == "pallas":
                # Bands go through the SAME Pallas kernel as the interior:
                # XLA may contract mul+add chains (FMA) differently per
                # compiled graph, so a jnp-evaluated band next to a
                # Pallas-computed interior breaks the overlap bit-match
                # contract for product-bearing programs (the advection
                # term u*dc/dx + v*dc/dy of advection_diffusion).
                if n_col > 1:
                    vals = _as_dict(apply_full(
                        _full_input(slabs, ex),
                        row_offset=row0, rows_global=r_glob,
                        col_offset=col0, cols_global=c_glob,
                    ))
                    return {f: v[..., h:-h, h:-h] for f, v in vals.items()}
                vals = _as_dict(apply_full(
                    _full_input(slabs, ex), row_offset=row0, rows_global=r_glob
                ))
                return {f: v[..., h:-h, :] for f, v in vals.items()}
            ex = ex or None
            state = slabs[state_f] if n_out == 1 else slabs
            if n_col > 1:
                return _as_dict(slab_sweep(program, state, row0, r_glob, col0,
                                           c_glob, extras=ex))
            return _as_dict(slab_sweep(program, state, row0, r_glob, extras=ex))

        full = slice(None)
        top = sweep(slice(None, 3 * h), full, off_r - h, off_c - h)
        bottom = sweep(slice(-3 * h, None), full, off_r + r_loc - 2 * h, off_c - h)
        if n_col == 1:
            return top, bottom, None, None
        left = sweep(slice(h, h + r_loc), slice(None, 3 * h), off_r, off_c - h)
        right = sweep(
            slice(h, h + r_loc), slice(-3 * h, None), off_r, off_c + c_loc - 2 * h
        )
        return top, bottom, left, right

    def _ret(vals):
        """shard_map return: bare array (single-output) or tuple in
        ``out_fields`` order (multi-output)."""
        if n_out == 1:
            return vals[state_f]
        return tuple(vals[f] for f in out_fields)

    def local_step(*blocks: Array):
        env = dict(zip(fields, blocks))
        states = {f: env[f] for f in out_fields}
        aux = {f: env[f] for f in aux_fields}
        if halo == 0 or (boundary == "ring" and n_row == 1 and n_col == 1):
            # Full grid present locally (or no spatial coupling at all): the
            # single-device lowering's boundary handling is already correct.
            # (Zero mode with halo > 0 still needs its zero extension, which
            # the general path's single-shard zero pads provide for free.)
            return _ret(_as_dict(apply_full(_full_input(states, aux))))
        block = states[state_f]
        r_loc, c_loc = block.shape[-2], block.shape[-1]

        if boundary == "zero":
            # Every owned point computed from the exchanged block; the zero
            # bands at uncovered grid edges (ppermute fill / single-shard
            # pads) are the wanted extension, so the single-device kernel's
            # OWN ring — evaluated on garbage halo-edge data — lands
            # entirely in the sliced-off frame. Columns get a local zero pad
            # when unsharded (free: no collective), keeping the kept region
            # at [halo:halo+r_loc, halo:halo+c_loc] either way.
            exchanged = _exchange_all(env)
            zs = {f: exchanged[f] for f in out_fields}
            za = {
                f: _pad_to_halo(exchanged.get(f, aux[f]), fhalos[f])
                for f in aux_fields
            }
            if n_col == 1:
                cp = [(0, 0)] * (block.ndim - 1) + [(halo, halo)]
                zs = {f: jnp.pad(a, cp) for f, a in zs.items()}
                za = {f: jnp.pad(a, cp) for f, a in za.items()}
            vals = _as_dict(apply_full(_full_input(zs, za)))
            return _ret({
                f: vals[f][..., halo : halo + r_loc, halo : halo + c_loc]
                .astype(states[f].dtype)
                for f in out_fields
            })

        off_r, off_c, r_glob, c_glob = _offsets(block)

        # overlap needs a non-empty interior after shaving the halo bands.
        can_overlap = overlap and r_loc > 2 * halo and (n_col == 1 or c_loc > 2 * halo)
        if can_overlap:
            # Interior first in program order: it reads only the unpadded
            # blocks, so the exchange's ppermutes have no consumers before it
            # and the latency-hiding scheduler is free to run them behind it.
            interior = _inner_interior(states, aux, off_r, off_c, r_glob, c_glob)

        # ONE merged exchange round covers every field that moves (all
        # evolving fields at the chain radius, aux fields at their own).
        exchanged = _exchange_all(env)
        padded_states = {f: exchanged[f] for f in out_fields}
        padded_aux = {
            f: _pad_to_halo(exchanged.get(f, aux[f]), fhalos[f])
            for f in aux_fields
        }

        if not can_overlap:
            vals = _inner_padded(
                padded_states, padded_aux, off_r, off_c, r_glob, c_glob,
                r_loc, c_loc,
            )
            return _ret({f: vals[f].astype(states[f].dtype) for f in out_fields})

        top, bottom, left, right = _edge_bands(
            padded_states, padded_aux, off_r, off_c, r_glob, c_glob, r_loc, c_loc
        )
        out = {}
        for f in out_fields:
            mid = interior[f]
            if n_col > 1:
                mid = jnp.concatenate([left[f], mid, right[f]], axis=-1)
            vals = jnp.concatenate([top[f], mid, bottom[f]], axis=-2)
            out[f] = vals.astype(states[f].dtype)
        return _ret(out)

    mapped = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(spec,) * len(fields),
        out_specs=spec if n_out == 1 else (spec,) * n_out,
        check_vma=False,
    )

    @jax.jit
    def _run(arrays):
        return mapped(*arrays)

    def _record_halo_model(arrays) -> None:
        """Per-field PER-CHIP model bytes for the exchange this call issues
        — the ``halo.model_bytes.<field>`` counters the drift detector
        compares against measured collective-permute bytes
        (``repro.dist.halo.wire_drift_report``) — plus a ``halo.exchange``
        flight-recorder event per round. Skipped while tracing: a
        lowered-but-instrumented step must not count trace-time calls."""
        reg = metrics.current()
        if (reg is None and events.current() is None) or metrics.has_tracer(arrays):
            return
        events.record(
            "halo.exchange", program=program.name, halo=halo,
            fields=[f for f in fields if fhalos[f]], merged=merge_exchange,
        )
        if reg is None:
            return
        from repro.dist.halo import call_exchange_bytes

        reg.inc("halo.exchange_rounds")
        per_field = call_exchange_bytes(
            program, arrays[0].shape, (n_depth, n_row, n_col),
            itemsize={f: a.dtype.itemsize for f, a in zip(fields, arrays)},
        )
        for f, nbytes in per_field.items():
            reg.inc(f"halo.model_bytes.{f}", nbytes)

    def step(x: Array | Mapping[str, Array]) -> Array:
        arrays = resolve_field_arrays(program, x, ndim=3)
        d, r, c = arrays[0].shape
        if n_depth > 1 and d % n_depth:
            raise ValueError(f"depth {d} not divisible by {n_depth} {depth_axis!r} shards")
        for extent, n_sh, ax, what, remedy in (
            (r, n_row, row_axis, "rows", "columns (col_axis=...)"),
            (c, n_col, col_axis, "cols", "rows (row_axis=...)"),
        ):
            if n_sh > 1:
                if extent % n_sh:
                    raise ValueError(
                        f"{what} {extent} not divisible by {n_sh} {ax!r} shards"
                    )
                if extent // n_sh < halo:
                    raise ValueError(
                        f"{what}/shard {extent // n_sh} < inferred halo {halo} "
                        f"(chain radius of {program.name!r}): too many {what} "
                        f"shards for the single-neighbour halo exchange — use "
                        f"fewer, or shard {remedy} instead"
                    )
        if halo > 0 and (n_row > 1 or n_col > 1):
            _record_halo_model(arrays)
        out = _run(arrays)
        if n_out == 1:
            return out
        return dict(zip(out_fields, out))

    return metrics.instrument_call(step, f"ir.lower_sharded.{program.name}")
