"""Milliseconds per server batch in the traced window spent waiting for
the batch's result on the device (``block_until_ready``): the
``serve.forecast.device_wait`` span, averaged. Program span."""

from bench import spans


def read(run):
    return spans.mean_ms(run, "device_wait")
