"""Simulated grid-point updates per second, in billions: grid points times
simulated steps of the calls done in the window, over the whole window
(output copies included). Host clock."""

def read(run):
    n = run.window["counts"].get("grid_updates")
    return n / run.window["window_s"] / 1e9 if n else None
