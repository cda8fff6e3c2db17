"""Seeded fields, made on the device in one jitted call.

A field is a smooth part (a few random low-wavenumber modes per level, the
synoptic scale) plus white noise at a tenth of its size (the grid scale the
diffusion acts on). Every seed gets the same shapes and the same kind of
field; only the values differ.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = 8
NOISE = 0.1


def key(seed: int, stream: int = 0) -> jax.Array:
    """A key from a seed of up to 64 bits, split into two 32-bit words so
    that no seed overflows; ``stream`` tells independent fields apart."""
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), stream)


def _smooth(k, shape):
    *lead, rows, cols = shape
    k_amp, k_phase, k_wave, k_noise = jax.random.split(k, 4)
    amp = jax.random.normal(k_amp, (MODES, *lead, 1, 1)) / jnp.sqrt(MODES)
    phase = jax.random.uniform(k_phase, (MODES, *lead, 1, 1), maxval=2 * jnp.pi)
    waves = jax.random.randint(k_wave, (MODES, 2), 1, 9).astype(jnp.float32)
    r = jnp.arange(rows, dtype=jnp.float32)[:, None] / rows
    c = jnp.arange(cols, dtype=jnp.float32)[None, :] / cols
    out = NOISE * jax.random.normal(k_noise, tuple(shape))
    for m in range(MODES):
        out = out + amp[m] * jnp.sin(2 * jnp.pi * (waves[m, 0] * r + waves[m, 1] * c) + phase[m])
    return out


@functools.partial(jax.jit, static_argnames=("shape", "sharding"))
def _smooth_jit(k, shape, sharding=None):
    out = _smooth(k, shape)
    return out if sharding is None else jax.lax.with_sharding_constraint(out, sharding)


def smooth_field(seed: int, stream: int, shape: tuple[int, ...], sharding=None) -> jax.Array:
    """One float32 field of ``shape`` (levels, rows, cols), laid out with
    ``sharding`` where given."""
    return _smooth_jit(key(seed, stream), tuple(shape), sharding)


@functools.partial(jax.jit, static_argnames=("members", "shape", "scale"))
def _ensemble(k, members, shape, scale):
    k_base, k_pert = jax.random.split(k)
    base = _smooth(k_base, shape)
    return base[None] + scale * _smooth(k_pert, (members, *shape))


def ensemble(seed: int, members: int, shape: tuple[int, ...], scale: float) -> jax.Array:
    """``members`` fields: one seeded base field plus a seeded perturbation
    of relative size ``scale`` for each member, as a (members, *shape)
    array."""
    return _ensemble(key(seed, 0), members, tuple(shape), float(scale))
