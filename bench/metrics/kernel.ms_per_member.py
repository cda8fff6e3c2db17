"""Device milliseconds of the Pallas kernels per member-step answered, on
the slowest device. Device trace."""

from bench import readers


def read(run):
    return readers.ms_per(run, "kernel_s", "members")
