"""Member-steps answered per second over the whole window. Host clock."""

def read(run):
    n = run.window["counts"].get("members")
    return n / run.window["window_s"] if n else None
