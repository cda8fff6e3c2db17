"""The served step's share of the HBM rate: the logical bytes of the fields
the window's batches read and wrote (the server's ``field_bytes``), over
the device's busy time, over the peak HBM rate of ``peaks.json``, in
percent; the slowest device's. Nothing where the program counts no field
bytes. Device trace and program counter."""


def read(run):
    n = run.window["counts"].get("field_bytes")
    busy = max((d["busy_s"] for d in run.trace["devices"].values()), default=0.0)
    return 100 * n / busy / run.peaks["hbm_bytes_per_s"] if n and busy else None
