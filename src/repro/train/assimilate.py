"""Variational data assimilation on the IR: fit a coefficient field.

The first end-to-end consumer of the derived adjoints
(:mod:`repro.ir.autodiff`): recover ``hdiff_coupled_program``'s
spatially-varying diffusion coefficient from observations of the diffused
state. The forward model is any ``build_backend(..., differentiable=True)``
lowering — reference for CI, Pallas or the sharded mesh for scale — so the
fit exercises exactly the gradient path the conformance matrix certifies,
and the optimizer is the shipped :mod:`repro.optim` stack (no separate
"training" codepath: the same AdamW/Adafactor and global-norm clip), with
every loss watched by a :class:`SpikeDetector`.

The 3D-Var-style setup: observations ``y = M(u0, c*)`` of a known prior
state ``u0`` under the true coefficients ``c*``, minimise ``J(c) = mean((M(
u0, c) - y)^2)`` from a flat first guess. The coefficient only enters at
interior points (the boundary ring passes through), so ring gradients are
exactly zero and the ring keeps its first-guess values — the interior
converges, which is what the >=10x loss-drop acceptance asserts.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.ir.graph import repeat
from repro.ir.lower_batched import build_backend
from repro.ir.programs import hdiff_coupled_program, smagorinsky_coeff
from repro.obs import events, metrics
from repro.optim import OptimizerConfig, clip_by_global_norm, make_optimizer

Array = jax.Array


class SpikeDetector:
    """Flags loss spikes through ``repro.obs``: a loss is a spike when it
    is non-finite, or exceeds ``factor`` x the running median of the last
    ``window`` recorded losses (after ``warmup`` steps — the first losses
    of a fresh fit legitimately swing). Every spike bumps the
    ``train.loss_spikes`` counter and records a structured
    ``train.loss_spike`` event carrying step/loss/threshold, so a loss
    excursion is in the flight recorder with its context, not just a line
    lost in stdout. Finite spikes still enter the history, so a genuine
    regime change re-centres the median instead of flagging forever."""

    def __init__(self, factor: float = 5.0, warmup: int = 5, window: int = 50):
        self.factor = factor
        self.warmup = warmup
        self.window = window
        self.losses: list[float] = []
        self.spikes: list[tuple[int, float]] = []

    def record(self, step: int, loss: float) -> bool:
        loss = float(loss)
        finite = np.isfinite(loss)
        threshold = None
        spike = not finite
        if finite and len(self.losses) > self.warmup:
            med = float(np.median(self.losses[-self.window:]))
            if med > 0:
                threshold = self.factor * med
                spike = loss > threshold
        if finite:
            self.losses.append(loss)
        if spike:
            self.spikes.append((step, loss))
            metrics.inc("train.loss_spikes")
            events.record("train.loss_spike", step=step, loss=loss,
                          threshold=threshold, factor=self.factor)
        return spike


@dataclasses.dataclass(frozen=True)
class AssimilationConfig:
    """One coefficient-field fit.

    ``backend`` / ``mesh_shape`` / ``interpret`` choose the differentiable
    lowering of the forward model (any conformance backend name);
    ``k`` temporally blocks it (``repeat(p, k)`` — the observation operator
    then spans k sweeps and the adjoint reverses all of them)."""

    steps: int = 80
    learning_rate: float = 3e-3
    optimizer: str = "adamw"
    grad_clip: float = 1.0
    backend: str = "reference"
    mesh_shape: tuple[int, int] | None = None
    interpret: bool | None = None
    k: int = 1
    limit: bool = True


@dataclasses.dataclass
class FitResult:
    coeff: Array
    losses: list[float]
    spikes: list[tuple[int, float]]

    @property
    def loss_ratio(self) -> float:
        """First-to-best loss improvement factor (the acceptance metric)."""
        return self.losses[0] / min(self.losses)


def forward_model(cfg: AssimilationConfig) -> Callable:
    """The differentiable observation operator ``{u, coeff} -> u_k``."""
    p = hdiff_coupled_program(limit=cfg.limit)
    if cfg.k > 1:
        p = repeat(p, cfg.k)
    return build_backend(
        p,
        cfg.backend,
        mesh_shape=cfg.mesh_shape,
        interpret=cfg.interpret,
        differentiable=True,
    )


def synthetic_observations(
    u0: Array, coeff_true: Array, cfg: AssimilationConfig
) -> Array:
    """Noise-free observations of the true-coefficient forward model."""
    return forward_model(cfg)({"u": u0, "coeff": coeff_true})


def true_coefficients(shape: Sequence[int], seed: int = 0) -> Array:
    """The Smagorinsky-style target field every test/benchmark fits
    (:func:`repro.ir.programs.smagorinsky_coeff` over unit noise)."""
    noise = jax.random.normal(jax.random.PRNGKey(seed), tuple(shape))
    return jnp.asarray(smagorinsky_coeff(noise))


def fit_coefficient_field(
    u0: Array,
    observations: Array,
    cfg: AssimilationConfig = AssimilationConfig(),
    coeff_init: Array | None = None,
) -> FitResult:
    """Minimise the observation misfit over the coefficient field.

    Plain full-batch gradient descent with the shipped optimizer stack:
    ``jax.value_and_grad`` through the differentiable lowering (the derived
    adjoint sweeps), global-norm clip, AdamW/Adafactor update, every loss
    through a :class:`SpikeDetector` so a diverging fit lands in the
    flight recorder."""
    fwd = forward_model(cfg)
    if coeff_init is None:
        coeff_init = jnp.full(u0.shape, 0.025, u0.dtype)

    def loss_fn(coeff):
        out = fwd({"u": u0, "coeff": coeff})
        return jnp.mean(jnp.square(out - observations))

    loss_and_grad = jax.jit(jax.value_and_grad(loss_fn))
    opt_cfg = OptimizerConfig(
        name=cfg.optimizer,
        learning_rate=cfg.learning_rate,
        weight_decay=0.0,  # shrinking coefficients toward 0 is not a prior
        grad_clip=cfg.grad_clip,
        warmup_steps=0,
        total_steps=cfg.steps,
    )
    init_fn, update_fn = make_optimizer(opt_cfg)
    coeff = coeff_init
    state = init_fn(coeff)
    detector = SpikeDetector()
    losses: list[float] = []
    for step in range(cfg.steps):
        loss, grad = loss_and_grad(coeff)
        losses.append(float(loss))
        detector.record(step, float(loss))
        grad, _gnorm = clip_by_global_norm(grad, cfg.grad_clip)
        coeff, state = update_fn(grad, state, coeff)
    return FitResult(coeff=coeff, losses=losses, spikes=detector.spikes)
