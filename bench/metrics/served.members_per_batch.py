"""Members per server batch in the traced window: the server's
``stats["members"] / stats["batches"]``. Program counter."""

def read(run):
    c = run.window["counts"]
    return c["members"] / c["batches"] if c.get("batches") else None
