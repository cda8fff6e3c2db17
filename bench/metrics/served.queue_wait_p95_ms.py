"""The 95th percentile of the traced window's requests' waits in the
server's queue, from submit to dispatch (``serve.forecast.queue_wait``), in
milliseconds. Program span."""

from bench import spans


def read(run):
    return spans.queue_wait_p95_ms(run)
