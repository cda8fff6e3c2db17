"""Device milliseconds per simulated step of the ops that are neither a
kernel nor a collective (the pad and crop around the kernel, the shard's
pad to its halo, layout copies), on the slowest device. Device trace."""

from bench import readers


def read(run):
    return readers.ms_per(run, "glue_s", "steps")
