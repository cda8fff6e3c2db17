"""Host milliseconds per server batch in the traced window: each
``serve.forecast.step`` span less its ``device_wait`` child, averaged.
Program span."""

from bench import spans


def read(run):
    return spans.host_ms(run)
