"""What several metric readers share."""

from __future__ import annotations


def ms_per(run, key: str, count: str) -> float | None:
    """Milliseconds of ``key`` per unit of ``count`` on the slowest device."""
    n = run.window["counts"].get(count)
    if not n:
        return None
    return 1e3 * max(d[key] for d in run.trace["devices"].values()) / n


def idle_share(run) -> float | None:
    devs = list(run.trace["devices"].values())
    if not devs:
        return None
    busy = sum(d["busy_s"] for d in devs) / len(devs)
    return 100 * (1 - busy / run.trace["window_s"])
