"""The Pallas kernels' share of the HBM rate: the bytes of each kernel call's
distinct operand and result arrays (an array passed as several operands
counts once), over the kernels' summed device time, over the peak HBM
rate of ``peaks.json``, in percent. The slowest device's (lowest) share.
Device trace."""

def read(run):
    shares = [d["kernel_bytes"] / d["kernel_s"] for d in run.trace["devices"].values()
              if d["kernel_s"] > 0]
    return 100 * min(shares) / run.peaks["hbm_bytes_per_s"] if shares else None
