"""Milliseconds per server batch in the traced window spent in the cached
batched call until it returns: the ``serve.forecast.dispatch`` span,
averaged. Program span."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "dispatch")
