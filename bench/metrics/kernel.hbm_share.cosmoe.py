"""The five-field kernel's share of the HBM rate, counted as
``kernel.hbm_share`` counts it (distinct operand and result bytes over the
kernels' device time over the peak HBM rate), in percent. Device trace."""

import os

from bench import harness

_shared = harness.load_module(os.path.join(os.path.dirname(__file__), "kernel.hbm_share.py"),
                              "bench_metric_kernel.hbm_share")


def read(run):
    return _shared.read(run)
