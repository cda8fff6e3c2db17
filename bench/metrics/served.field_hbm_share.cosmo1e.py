"""The served step's share of one chip's HBM rate on a mesh: the logical
bytes of the fields each chip's shards read and wrote in the window
(``chip_field_bytes``, the server's ``field_bytes`` over the mesh's chips),
over the busiest chip's busy time, over the peak HBM rate of
``peaks.json``, in percent. Nothing where the driver counts no field
bytes. Device trace and program counter."""


def read(run):
    n = run.window["counts"].get("chip_field_bytes")
    busy = max((d["busy_s"] for d in run.trace["devices"].values()), default=0.0)
    return 100 * n / busy / run.peaks["hbm_bytes_per_s"] if n and busy else None
