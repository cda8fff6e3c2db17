"""The plain reference of the benchmarked programs, in straightforward jnp.

It imports nothing of the system under test. COSMO horizontal diffusion with
the flux limiter (SPARTA paper, Eq. 1-4): a 5-point Laplacian, four fluxes
zeroed where they point up-gradient, and the update
``out = psi - coeff * ((F_r - F_rm) + (G_c - G_cm))`` on the interior. The
radius-2 ring of every (rows, cols) plane passes through unchanged. Leading
axes (levels, members) are independent.

``dtype`` is the precision the arithmetic runs in: float32 as configured,
or bfloat16 for the control that has to read as not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HALO = 2


def _limit(d, g):
    return jnp.where(d * g <= 0, d, jnp.zeros_like(d))


def hdiff(psi, coeff: float):
    """One limited hdiff sweep over the last two axes of ``psi``."""
    lap = (
        4.0 * psi[..., 1:-1, 1:-1]
        - psi[..., 2:, 1:-1]
        - psi[..., :-2, 1:-1]
        - psi[..., 1:-1, 2:]
        - psi[..., 1:-1, :-2]
    )
    lap_c = lap[..., 1:-1, 1:-1]
    psi_c = psi[..., 2:-2, 2:-2]
    flx_r = _limit(lap[..., 2:, 1:-1] - lap_c, psi[..., 3:-1, 2:-2] - psi_c)
    flx_rm = _limit(lap_c - lap[..., :-2, 1:-1], psi_c - psi[..., 1:-3, 2:-2])
    flx_c = _limit(lap[..., 1:-1, 2:] - lap_c, psi[..., 2:-2, 3:-1] - psi_c)
    flx_cm = _limit(lap_c - lap[..., 1:-1, :-2], psi_c - psi[..., 2:-2, 1:-3])
    interior = psi_c - jnp.asarray(coeff, psi.dtype) * ((flx_r - flx_rm) + (flx_c - flx_cm))
    return psi.at[..., HALO:-HALO, HALO:-HALO].set(interior)


def forecast(psi, steps, coeff: float, dtype=jnp.float32):
    """``steps`` hdiff sweeps (a traced count is fine), computed in
    ``dtype``; returned as float32."""
    out = jax.lax.fori_loop(0, steps, lambda _, s: hdiff(s, coeff), psi.astype(dtype))
    return out.astype(jnp.float32)


def misfit_gradient(x, y, coeff: float, dtype=jnp.float32):
    """The gradient of ``J(x) = 0.5 * ||hdiff(x) - y||^2``, computed in
    ``dtype``; returned as float32."""
    x, y = x.astype(dtype), y.astype(dtype)
    out, vjp = jax.vjp(lambda a: hdiff(a, coeff), x)
    return vjp(out - y)[0].astype(jnp.float32)
