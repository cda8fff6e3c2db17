"""Device milliseconds of the gradient step's Pallas kernels (the forward,
the augmented forward and the adjoint) per step. Device trace."""

from bench import readers


def read(run):
    return readers.ms_per(run, "kernel_s", "gradient_steps")
