"""Milliseconds per server batch in the traced window spent slicing each
member's result out of the batch: the ``serve.forecast.unstack`` span,
averaged. Program span."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "unstack")
