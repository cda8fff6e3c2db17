"""Back-to-back assimilation steps through the derived adjoint kernels.

Each step computes the gradient of ``J(x) = 0.5 * ||hdiff(x) - y||^2`` with
respect to the state ``x`` through ``build_backend(hdiff, "pallas",
differentiable=True)`` (the forward kernel, then the augmented forward and
the adjoint kernels), where ``y`` is a seeded observation field, and
updates ``x <- x - learning_rate * grad``. The host waits for each step, as
an assimilation loop that watches its progress does.

Traffic parameters: ``learning_rate``, ``trace_seconds`` and ``limits``.

The check, on a step drawn from the seed and on the last step: the step's
gradient against the plain reference's gradient at the step's own input
(``grad_rel_err``), and the step's new state against the input minus the
learning rate times that reference gradient, as a share of the update's
size (``update_rel_err``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, fields, reference


class Driver:
    def __init__(self, config, traffic, seed, timed_path=None):
        from repro.ir import build_backend, hdiff_program

        self.coeff = config["coeff"]
        self.lr = traffic["learning_rate"]
        self.limits = traffic["limits"]
        shape = (config["depth"], config["rows"], config["cols"])
        fwd = build_backend(hdiff_program(self.coeff, limit=config["limit"]), "pallas",
                            differentiable=True)
        lr = self.lr

        def grad_step(x, y):
            g = jax.grad(lambda a: 0.5 * jnp.sum(jnp.square(fwd(a) - y)))(x)
            return x - lr * g, g

        step = grad_step if timed_path is None else timed_path(grad_step)
        self.x = fields.smooth_field(seed, 0, shape)
        self.y = fields.smooth_field(seed, 1, shape)
        self.compiled = jax.jit(step).lower(self.x, self.y).compile()
        self.programs = [self.compiled.as_text()]
        self.sample = compare.Sample(seed)
        self.steps = 0

    def _step(self) -> None:
        with jax.profiler.TraceAnnotation("bench.update"):
            x_new, g = self.compiled(self.x, self.y)
            jax.block_until_ready(x_new)
        self.sample.offer((self.x, x_new, g))
        self.x = x_new
        self.steps += 1

    def warm(self) -> None:
        for _ in range(2):
            self._step()

    def window(self, seconds: float) -> dict:
        steps0 = self.steps
        t0 = time.perf_counter()
        while True:
            self._step()
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        steps = self.steps - steps0
        return {"window_s": window_s, "attempted": steps, "failed": 0,
                "counts": {"gradient_steps": steps}}

    def release(self) -> None:
        self.x = self.compiled = None

    def check(self) -> dict:
        ref = jax.jit(lambda a, b: reference.misfit_gradient(a, b, self.coeff))
        grad_err = update_err = 0.0
        for x_in, x_out, g in self.sample.items():
            g_ref = ref(x_in, self.y)
            update = np.asarray(self.lr * g_ref)
            grad_err = max(grad_err, compare.rel_err(g, g_ref))
            moved = np.asarray(x_in, np.float64) - np.asarray(x_out, np.float64)
            update_err = max(update_err, compare.rel_err(moved, update))
        return {"grad_rel_err": (grad_err, self.limits["grad_rel_err"]),
                "update_rel_err": (update_err, self.limits["update_rel_err"])}


def control(config, traffic):
    """The control: the plain reference's gradient computed in bfloat16,
    put in the place of the program's gradient step."""
    coeff, lr = config["coeff"], traffic["learning_rate"]

    def wrap(grad_step):
        def step(x, y):
            g = reference.misfit_gradient(x, y, coeff, jnp.bfloat16)
            return x - lr * g, g
        return step

    return wrap
