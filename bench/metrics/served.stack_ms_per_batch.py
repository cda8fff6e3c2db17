"""Milliseconds per server batch in the traced window spent stacking the
members' fields into one batch: the ``serve.forecast.stack`` span,
averaged. Program span."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "stack")
