"""Gradient-and-update steps per second over the whole window. Host clock."""

def read(run):
    n = run.window["counts"].get("gradient_steps")
    return n / run.window["window_s"] if n else None
