"""Profiler trace of the measured window, and its reduction to layer numbers.

``capture`` records a ``jax.profiler`` trace; ``load`` reads it back into
plain lists (``{"devices": {"TPU:0": [[name, start_ns, dur_ns], ...]},
"host": [[name, start_ns, dur_ns], ...]}``), which is also the format of the
small recorded trace the tests check the reduction on; ``reduce`` turns
those lists into per-device numbers.

Device events come from each ``/device:TPU:<n>`` plane's "XLA Ops" line,
where every event is named by its full HLO instruction (``hlo.kind`` sorts
them into kernel, collective, container and glue). Host events are the
benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``); the
``bench.window`` span bounds the window. Device and host events share one
clock in the trace.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import shutil
from collections import defaultdict

from bench import hlo

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@contextlib.contextmanager
def capture(out_dir: str):
    """Traces the enclosed block into ``out_dir`` (emptied first)."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    jax.profiler.start_trace(out_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(trace_dir: str) -> dict:
    """The device op events and the benchmark's host spans of a trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE) and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name[len(DEVICE_PLANE):]] = [
                        [e.name, e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def measure(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def minus(a, b) -> float:
    """The length of ``a`` that ``b`` does not cover."""
    both = measure(list(a) + list(b))
    return both - measure(b)


def gaps(busy, t0: float, t1: float) -> list[tuple[float, float]]:
    """The parts of ``[t0, t1]`` that ``busy`` (merged) does not cover."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def _label(name: str) -> str:
    op = "tpu_custom_call" if hlo.is_kernel(name) else hlo.opcode(name)
    return f"{hlo.instruction_name(name)} {op}".strip()


def _attribute(host, gs: float, ge: float) -> list[tuple[str, float]]:
    """Splits the idle gap ``[gs, ge)`` by the host span each part fell in
    (the innermost where spans nest; "none" outside every span)."""
    spans, starts, longest = host
    lo = bisect.bisect_left(starts, gs - longest)
    hi = bisect.bisect_left(starts, ge)
    inside = [h for h in spans[lo:hi] if h[1] > gs]
    cuts = sorted({gs, ge, *(t for h in inside for t in h[:2] if gs < t < ge)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [h for h in inside if h[0] <= mid < h[1]]
        label = min(cover, key=lambda h: (h[1] - h[0], h[2]))[2] if cover else "none"
        out.append((label, (b - a) * 1e-9))
    return out


def reduce(events: dict) -> dict:
    """Per-device numbers over the ``bench.window`` span.

    For each device: ``busy_s`` (the union of every op's interval, loops
    included), ``kernel_s`` / ``glue_s`` / ``collective_s`` (summed op time
    by kind, containers left out), ``exposed_collective_s`` (collective time
    during which no kernel or glue op runs on that device),
    ``kernel_bytes`` (the kernels' distinct-buffer bytes) and
    ``collective_bytes`` (the collectives' result bytes). An op cut by the
    window's edge counts with the share of it inside, bytes too.
    Besides: ``window_s``, the device ops that took most time
    (``device_ops``, averaged over devices), and the idle time by the host
    span each part of it fell in (``idle_gaps``; "host:none" outside every
    span)."""
    spans = [h for h in events["host"] if h[0] == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"want one {WINDOW_SPAN} span in the trace, found {len(spans)}")
    t0 = spans[0][1]
    t1 = t0 + spans[0][2]
    host = sorted((s, s + d, n) for n, s, d in events["host"] if n != WINDOW_SPAN)
    host = (host, [h[0] for h in host], max((h[1] - h[0] for h in host), default=0))
    per_dev: dict[str, dict] = {}
    op_time: dict[str, float] = defaultdict(float)
    idle: dict[str, float] = defaultdict(float)
    for dev, evs in sorted(events["devices"].items()):
        num = defaultdict(float)
        by_kind: dict[str, list] = defaultdict(list)
        for name, start, dur in evs:
            s, e = max(start, t0), min(start + dur, t1)
            if e <= s:
                continue
            share = (e - s) / dur if dur > 0 else 0.0
            k = hlo.kind(name)
            by_kind[k].append((s, e))
            if k == "container":
                continue
            num[f"{k}_s"] += (e - s) * 1e-9
            op_time[_label(name)] += (e - s) * 1e-9
            if k == "kernel":
                num["kernel_bytes"] += hlo.distinct_buffer_bytes(name) * share
                num["kernel_calls"] += share
            elif k == "collective" and not hlo.opcode(name).endswith("-done"):
                num["collective_bytes"] += hlo.result_bytes(name) * share
        busy = union([iv for ivs in by_kind.values() for iv in ivs])
        num["busy_s"] = sum(e - s for s, e in busy) * 1e-9
        num["exposed_collective_s"] = minus(
            by_kind["collective"], by_kind["kernel"] + by_kind["glue"]) * 1e-9
        for k in ("kernel_s", "glue_s", "collective_s", "kernel_bytes", "kernel_calls",
                  "collective_bytes"):
            num.setdefault(k, 0.0)
        per_dev[dev] = dict(num)
        for gs, ge in gaps(busy, t0, t1):
            for label, secs in _attribute(host, gs, ge):
                idle[f"host:{label}"] += secs
    n_dev = max(len(per_dev), 1)
    top = lambda d: [[k, v / n_dev] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]  # noqa: E731
    return {"window_s": (t1 - t0) * 1e-9, "devices": per_dev,
            "device_ops": top(op_time), "idle_gaps": top(idle)}
