"""The server's own spans of the measured window, for the serving layer's
readers.

``ForecastServer`` records a ``serve.forecast.step`` span per batch, with
its phases (admit, stack, dispatch, device_wait, unstack, retire) as
children, and a ``serve.forecast.queue_wait`` span per request, in the ring
of ``repro.obs.events``. Nothing steps the server after the window, so the
window's batches are the last ``counts["batches"]`` step spans of the ring
and its requests the last ``counts["members"]`` queue waits. Each function
returns None where the ring holds fewer (spans fell off it, or the program
records none).
"""

from __future__ import annotations

import statistics

import numpy as np

STEP = "serve.forecast.step"
QUEUE_WAIT = "serve.forecast.queue_wait"
PHASES = ("admit", "stack", "dispatch", "device_wait", "unstack", "retire")


def _ring() -> list:
    """The program's span ring; empty where the program keeps none."""
    from repro.obs import events

    spans = getattr(events, "spans", None)
    return spans() if spans is not None else []


def _last(spans: list, name: str, n: int) -> list | None:
    if not n:
        return None
    picked = [s for s in spans if s.name == name][-n:]
    return picked if len(picked) == n else None


def batches(run) -> list[dict] | None:
    """Seconds of each window batch: ``step`` and each phase by name."""
    spans = _ring()
    steps = _last(spans, STEP, run.window["counts"].get("batches", 0))
    if steps is None:
        return None
    rows = {s.id: {"step": s.duration_s} for s in steps}
    for s in spans:
        if s.parent in rows:
            rows[s.parent][s.name.rsplit(".", 1)[-1]] = s.duration_s
    if any(len(r) != len(PHASES) + 1 for r in rows.values()):
        return None
    return list(rows.values())


def mean_ms(run, phase: str) -> float | None:
    """Milliseconds of ``phase`` per window batch, on average."""
    rows = batches(run)
    return 1e3 * statistics.fmean(r[phase] for r in rows) if rows else None


def host_ms(run) -> float | None:
    """Milliseconds per window batch of the step's host self time: the
    step less its wait for the device."""
    rows = batches(run)
    return 1e3 * statistics.fmean(r["step"] - r["device_wait"] for r in rows) if rows else None


def queue_wait_p95_ms(run) -> float | None:
    """The 95th percentile of the window's requests' waits from submit to
    dispatch, in milliseconds."""
    waits = _last(_ring(), QUEUE_WAIT, run.window["counts"].get("members", 0))
    return float(np.percentile([w.duration_s for w in waits], 95)) * 1e3 if waits else None
