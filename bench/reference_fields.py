"""The plain reference of the multi-field program (``hdiff_fields``): each
field diffused alone by ``bench/reference.hdiff`` with its own coefficient.
Fields never mix, so a block of one field is computed as the whole would be.

It imports nothing of the system under test. ``dtype`` is the precision the
arithmetic runs in: float32 as configured, or bfloat16 for the control.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench import reference


def hdiff_fields(fields, coeffs, dtype=jnp.float32):
    """One limited hdiff sweep of each field of ``fields`` (``{name: (...,
    rows, cols)}``) with ``coeffs[name]``, computed in ``dtype`` and
    returned in each field's own dtype."""
    return {f: reference.hdiff(x.astype(dtype), coeffs[f]).astype(x.dtype)
            for f, x in fields.items()}


def forecast(fields, steps, coeffs, dtype=jnp.float32):
    """``steps`` sweeps of each field of ``fields`` (a traced count is
    fine), computed in ``dtype``; returned as float32."""
    return {f: reference.forecast(x, steps, coeffs[f], dtype) for f, x in fields.items()}
