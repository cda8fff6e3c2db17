"""The 95th percentile of every member request's latency in the window, from
its submit to the end of the server step that made its result ready, in
milliseconds. Host clock."""

import numpy as np


def read(run):
    lat = run.window.get("latencies_s")
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
