"""Horizontal diffusion (hdiff) — the paper's compound stencil (Eq. 1-4, Alg. 1).

Two variants, both reproduced bit-for-bit against NumPy loop oracles in
``tests/test_hdiff.py``:

  * :func:`hdiff` — the full COSMO kernel with the *flux limiter*
    (Eq. 2-3: a flux is zeroed when it points up-gradient). This is the
    production kernel; it is nonlinear due to the compare/select.
  * :func:`hdiff_simple` — Algorithm 1's unlimited polynomial form (the
    version used by the prior FPGA accelerators NERO/NARMADA the paper
    compares against). Linear in the input, which the property tests
    exploit.

Grid convention: ``(depth, rows, cols)`` (the paper's ``D x R x C``,
evaluated on 64 x 256 x 256). Depth is embarrassingly parallel. All
computation happens on the interior ``[2 : -2]`` ring in rows and cols —
a radius-2 halo, because flux reads the Laplacian of a neighbour which in
turn reads the neighbour's neighbour. Boundary cells pass through.

Stage structure (what the multi-AIE mapping splits across cores):

  stage 1 (Laplacian core):  L = lap(psi)              5-pt, 5 MACs
  stage 2 (flux core):       F = limit(dL_r, dpsi_r)   diff + cmp + select
                             G = limit(dL_c, dpsi_c)
  stage 3 (output):          out = psi - C * (F_r - F_rm + G_c - G_cm)

The *fused* execution policies in :mod:`repro.core.compound` keep L, F, G
in VMEM (the TPU analogue of the paper's accumulator-register residency /
cascade forwarding); the *staged* policy materialises each to HBM (the
single-core / CPU-baseline analogue).
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from repro.core.stencils import StencilSpec
from repro.ir.programs import hdiff_program

Array = jax.Array

# Per-output-point op counts for the analytical model (§3.1), DERIVED from
# the IR dataflow graph (repro.ir.programs.hdiff_program) rather than
# hand-counted: 5 Laplacians x 5 MACs (the lap op is consumed at the 5 star
# offsets); 4 fluxes x (1 sub + 1 mul [limiter product] + 1 cmp + 1 select);
# output: 4 adds + 1 MAC (coeff); 13 distinct reads (the composed star-of-
# star footprint); radius 2. tests/test_ir_graph.py pins the paper's
# literal numbers (26 MACs / 20 ops / 13 reads / r=2) against this.
_DERIVED = hdiff_program().spec()
HDIFF_SPEC = StencilSpec(
    name="hdiff",
    macs=_DERIVED.macs,
    other_ops=_DERIVED.other_ops,
    reads=_DERIVED.reads,
    radius=_DERIVED.radius,
)

# Radius of the compound stencil (flux-of-laplacian): 2 cells, inferred.
HALO = HDIFF_SPEC.radius


def _limit(dlap: Array, dpsi: Array) -> Array:
    """Flux limiter (Eq. 2-3): keep the flux only if it is down-gradient.

    ``F = dL if dL * dpsi <= 0 else 0``
    """
    return jnp.where(dlap * dpsi <= 0, dlap, jnp.zeros_like(dlap))


def _hdiff_interior(psi: Array, coeff: Array | float, *, limit: bool) -> Array:
    """Computes hdiff output on the interior (shape shrinks by 2*HALO).

    ``psi``: ``(..., R, C)``. Returns ``(..., R-4, C-4)``.
    """
    # Laplacian on the radius-1 interior: shape (..., R-2, C-2).
    lap = (
        4.0 * psi[..., 1:-1, 1:-1]
        - psi[..., 2:, 1:-1]
        - psi[..., :-2, 1:-1]
        - psi[..., 1:-1, 2:]
        - psi[..., 1:-1, :-2]
    )

    # Indexing guide: lap[..., i, j] corresponds to psi[..., i+1, j+1].
    # We need, for output point (r, c) with r,c in [2, N-2):
    #   row-fluxes  F(r, c)   = limit(L[r+1,c] - L[r,c],  psi[r+1,c]-psi[r,c])
    #               F(r-1, c) = limit(L[r,c] - L[r-1,c],  psi[r,c]-psi[r-1,c])
    #   col-fluxes  G(r, c), G(r, c-1) analogously.
    # Slices of `lap` covering output rows [2, R-2) => lap rows [1, R-3).
    lap_c = lap[..., 1:-1, 1:-1]   # L[r, c]
    lap_rp = lap[..., 2:, 1:-1]    # L[r+1, c]
    lap_rm = lap[..., :-2, 1:-1]   # L[r-1, c]
    lap_cp = lap[..., 1:-1, 2:]    # L[r, c+1]
    lap_cm = lap[..., 1:-1, :-2]   # L[r, c-1]

    psi_c = psi[..., 2:-2, 2:-2]
    psi_rp = psi[..., 3:-1, 2:-2]
    psi_rm = psi[..., 1:-3, 2:-2]
    psi_cp = psi[..., 2:-2, 3:-1]
    psi_cm = psi[..., 2:-2, 1:-3]

    flx_r = lap_rp - lap_c   # F at (r+1/2, c)
    flx_rm = lap_c - lap_rm  # F at (r-1/2, c)
    flx_c = lap_cp - lap_c   # G at (r, c+1/2)
    flx_cm = lap_c - lap_cm  # G at (r, c-1/2)

    if limit:
        flx_r = _limit(flx_r, psi_rp - psi_c)
        flx_rm = _limit(flx_rm, psi_c - psi_rm)
        flx_c = _limit(flx_c, psi_cp - psi_c)
        flx_cm = _limit(flx_cm, psi_c - psi_cm)

    if isinstance(coeff, jax.Array) and coeff.ndim >= 2:
        coeff = coeff[..., 2:-2, 2:-2]
    return psi_c - coeff * ((flx_r - flx_rm) + (flx_c - flx_cm))


def hdiff(psi: Array, coeff: Array | float = 0.025) -> Array:
    """Full COSMO horizontal diffusion with flux limiter (Eq. 1-4).

    Args:
      psi: input field ``(..., R, C)`` — typically ``(D, R, C)``.
      coeff: diffusion coefficient ``C^n_{r,c,d}`` — scalar or a field
        broadcastable to ``psi`` (the paper parameterises per grid point).

    Returns:
      Same shape as ``psi``; interior diffused, radius-2 border unchanged.
    """
    interior = _hdiff_interior(psi, coeff, limit=True)
    return psi.at[..., HALO:-HALO, HALO:-HALO].set(interior.astype(psi.dtype))


def hdiff_simple(psi: Array, coeff: Array | float = 0.025) -> Array:
    """Unlimited hdiff (Algorithm 1 / NERO-NARMADA form). Linear in ``psi``
    up to the constant passthrough of the boundary."""
    interior = _hdiff_interior(psi, coeff, limit=False)
    return psi.at[..., HALO:-HALO, HALO:-HALO].set(interior.astype(psi.dtype))


def hdiff_fields(
    fields: Mapping[str, Array], coeffs: Mapping[str, float], *, limit: bool = True
) -> dict[str, Array]:
    """Each field diffused alone with its own coefficient: ``{f:
    hdiff(fields[f], coeffs[f])}`` (``hdiff_simple`` when ``limit`` is
    False). The plain reference of ``repro.ir.hdiff_fields_program``."""
    one = hdiff if limit else hdiff_simple
    return {f: one(x, coeffs[f]) for f, x in fields.items()}


def hdiff_staged(psi: Array, coeff: Array | float = 0.025, *, limit: bool = True) -> Array:
    """Stage-materialising hdiff: every stage is forced to HBM.

    This is the single-AIE / load-store-architecture baseline analogue used
    by ``benchmarks/fig9_designs.py``: the Laplacian field, the four flux
    fields, and the output are each produced by a separately jitted function
    with ``jax.block_until_ready`` barriers between them, so XLA cannot fuse
    across stages. Numerically identical to :func:`hdiff`.
    """
    lap_fn = jax.jit(_staged_lap)
    flux_fn = jax.jit(_staged_flux, static_argnames=("limit",))
    out_fn = jax.jit(_staged_out)

    lap = jax.block_until_ready(lap_fn(psi))
    flx = jax.block_until_ready(flux_fn(psi, lap, limit=limit))
    out = out_fn(psi, coeff, *flx)
    return out


def _staged_lap(psi: Array) -> Array:
    return (
        4.0 * psi[..., 1:-1, 1:-1]
        - psi[..., 2:, 1:-1]
        - psi[..., :-2, 1:-1]
        - psi[..., 1:-1, 2:]
        - psi[..., 1:-1, :-2]
    )


def _staged_flux(psi: Array, lap: Array, *, limit: bool):
    lap_c = lap[..., 1:-1, 1:-1]
    flx_r = lap[..., 2:, 1:-1] - lap_c
    flx_rm = lap_c - lap[..., :-2, 1:-1]
    flx_c = lap[..., 1:-1, 2:] - lap_c
    flx_cm = lap_c - lap[..., 1:-1, :-2]
    if limit:
        psi_c = psi[..., 2:-2, 2:-2]
        flx_r = _limit(flx_r, psi[..., 3:-1, 2:-2] - psi_c)
        flx_rm = _limit(flx_rm, psi_c - psi[..., 1:-3, 2:-2])
        flx_c = _limit(flx_c, psi[..., 2:-2, 3:-1] - psi_c)
        flx_cm = _limit(flx_cm, psi_c - psi[..., 2:-2, 1:-3])
    return flx_r, flx_rm, flx_c, flx_cm


def _staged_out(psi, coeff, flx_r, flx_rm, flx_c, flx_cm):
    if isinstance(coeff, jax.Array) and coeff.ndim >= 2:
        coeff = coeff[..., 2:-2, 2:-2]
    interior = psi[..., 2:-2, 2:-2] - coeff * ((flx_r - flx_rm) + (flx_c - flx_cm))
    return psi.at[..., 2:-2, 2:-2].set(interior.astype(psi.dtype))


def hdiff_flops(depth: int, rows: int, cols: int) -> int:
    """Total flops for one hdiff sweep (paper Eq. 5-7 op counts, as flops)."""
    interior = (rows - 2 * HALO) * (cols - 2 * HALO) * depth
    return interior * HDIFF_SPEC.flops


def hdiff_min_bytes(depth: int, rows: int, cols: int, itemsize: int = 4) -> int:
    """Minimum HBM traffic for one sweep: read grid + coeff once, write once.

    The paper's Eq. 8-9 count *algorithmic* element touches (25 + 8 per
    point) because an AIE core streams rows without a reuse cache; the TPU
    fused-kernel lower bound is compulsory traffic only — each input element
    is loaded into VMEM once and reused there (the B-block broadcast
    analogue). Reported both ways in benchmarks.
    """
    return (3 * depth * rows * cols) * itemsize


def hdiff_algorithmic_bytes(depth: int, rows: int, cols: int, itemsize: int = 4) -> int:
    """Paper Eq. 8-9 traffic model: every stencil read hits memory."""
    interior = (rows - 2 * HALO) * (cols - 2 * HALO) * depth
    reads = 5 * 5 * interior + 2 * 4 * interior  # Laplacian + flux streams
    writes = interior
    return (reads + writes) * itemsize
