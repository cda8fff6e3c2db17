"""The benchmark's harness: finds a cell's files by name and runs it.

``BENCHMARK.json`` (at the root, beside ``bench/``) names each cell
(``workloads``), its configuration and its traffic mix, and the metrics.
Everything else is found by name under ``bench/``:

  configs/<config>.json     the deployment: grid, program, members, source
  traffic/<traffic>.json    the mix: its driver, parameters, check limits
  drivers/<driver>.py       the general generator the mix names
  metrics/<metric>.py       one reader per metric: ``read(run) -> float | None``

so a new cell, configuration, mix or metric is a new file and an entry in
``BENCHMARK.json``, never an edit here.

A run: refuse to start without a TPU (or with fewer chips than the cell
asks for); set up the driver (fields on the device from the seed, the
compiled step, a warm-up of every shape); measure for ``seconds`` (with
``trace=1``, for the traffic's ``trace_seconds`` at most, under the
profiler); read the device memory peak; free the program's state; compare
what the window produced with the plain reference; print the result.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench: str


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    setup_s: float
    window: dict
    trace: dict | None
    peaks: dict


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    bench = os.path.join(root, "bench")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[workload]
    e2e = [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    return Cell(
        name=workload, chips=w["chips"],
        config=_json(os.path.join(bench, "configs", f"{w['config']}.json")),
        traffic=_json(os.path.join(bench, "traffic", f"{w['traffic']}.json")),
        end_to_end=e2e, per_layer=layer, bench=bench)


def device_info(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; ``NoChip`` where the
    machine has no TPU or fewer than ``chips`` of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks_for(kind: str, bench: str) -> dict:
    table = _json(os.path.join(bench, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
             t_start: float | None = None, require_chip: bool = True, timed_path=None):
    """Runs one cell and returns ``(result, checks)``; ``checks`` maps each
    compared number's name to ``(value, limit)``. ``timed_path`` wraps the
    program's timed step: the control (``bench/control.py``) and the tests
    put another computation in its place, so it skips the check that each
    compiled step holds a Pallas kernel; ``require_chip=False`` lets the
    tests drive a run on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = find_cell(workload, root)
    import jax

    from bench import tracing
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    if require_chip:
        device = device_info(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}
    peaks = peaks_for(device["kind"], cell.bench) if trace else {}
    compiles: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: compiles.append(time.perf_counter())
        if event in COMPILE_EVENTS else None)

    driver_mod = load_module(os.path.join(cell.bench, "drivers", f"{cell.traffic['driver']}.py"),
                             f"bench_driver_{cell.traffic['driver']}")
    driver = driver_mod.Driver(cell.config, cell.traffic, seed, timed_path=timed_path)
    driver.warm()
    if require_chip and timed_path is None:
        for text in driver.programs:
            if "tpu_custom_call" not in text:
                raise AssertionError(f"{workload}: a compiled step holds no Pallas kernel")
    setup_s = time.perf_counter() - t_start

    window_s = min(seconds, cell.traffic["trace_seconds"]) if trace else seconds
    trace_dir = os.path.join(root, ".bench_trace", workload)
    t0 = time.perf_counter()
    if trace:
        with tracing.capture(trace_dir), jax.profiler.TraceAnnotation("bench.window"):
            record = driver.window(window_s)
    else:
        with jax.profiler.TraceAnnotation("bench.window"):
            record = driver.window(window_s)
    t1 = time.perf_counter()
    window_compiles = sum(t0 <= t <= t1 for t in compiles)
    used = jax.devices()[: cell.chips]
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    reduction = None
    if trace:
        reduction = tracing.reduce(tracing.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        devs = list(reduction["devices"].values())[: cell.chips]
        device["busy_s"] = sum(d["busy_s"] for d in devs) / max(len(devs), 1)
        device["window_s"] = reduction["window_s"]

    driver.release()
    checks = dict(driver.check())
    checks["window_compiles"] = (float(window_compiles), 0.0)

    run = Run(setup_s=setup_s, window=record, trace=reduction, peaks=peaks)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        reader = load_module(os.path.join(cell.bench, "metrics", f"{m['name']}.py"),
                             f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = (record["failed"] == 0 and record["attempted"] > 0
               and all(v <= lim for v, lim in checks.values()))
    result = {"correct": correct, "attempted": record["attempted"], "failed": record["failed"],
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result, checks
