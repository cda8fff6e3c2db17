"""Collective bytes the trace shows on the chip that moves most (the
permutes' result bytes, as their HLO instructions state them), over the
per-chip model bytes of the window's halo exchanges that the server counts
(``halo_bytes``): 1.000 where the sharded path moves exactly the model's
bands, more where XLA adds traffic of its own. Nothing where the server
counts no exchange. Device trace and program counter."""


def read(run):
    model = run.window["counts"].get("halo_bytes")
    moved = max((d["collective_bytes"] for d in run.trace["devices"].values()), default=0.0)
    return moved / model if model else None
