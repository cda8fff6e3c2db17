"""repro: SPARTA-on-TPU — compound weather-stencil acceleration in JAX/Pallas."""

__version__ = "1.0.0"
