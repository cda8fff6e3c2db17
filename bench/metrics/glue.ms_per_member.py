"""Device milliseconds per member-step of the ops that are neither a kernel
nor a collective (the member stack, the row pad to equal tiles, the crop
and the per-member slices), on the slowest device. Device trace."""

from bench import readers


def read(run):
    return readers.ms_per(run, "glue_s", "members")
