"""Collective-permute bytes per chip per simulated step: the result bytes of
the permutes the trace shows, as their HLO instructions state them, on
the chip that moves most. Nothing where no permute ran. Device trace."""

def read(run):
    moved = max(d["collective_bytes"] for d in run.trace["devices"].values())
    steps = run.window["counts"].get("steps")
    return moved / steps if moved and steps else None
