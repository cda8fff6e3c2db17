"""Device milliseconds per member-step during which a collective runs and
no kernel or glue op does, on the slowest chip of the mesh. Nothing where
the trace holds no collective. Device trace."""

from bench import readers


def read(run):
    if not any(d["collective_s"] for d in run.trace["devices"].values()):
        return None
    return readers.ms_per(run, "exposed_collective_s", "members")
