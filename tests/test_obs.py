"""repro.obs: metrics registry, drift detector, run reports, profiler hooks,
and the instrumented layers that report through them.

Covers the observability contracts:

  * zero-overhead disabled path (module hooks are no-ops, the timer is the
    shared null singleton, nothing is recorded);
  * timer nesting records under the joined ``outer/inner`` path;
  * counter/gauge/timer reset;
  * ``instrument_call``: records when enabled, passes through when disabled,
    steps aside on tracer arguments (and never changes the result);
  * drift detector inside/outside tolerance + registry side channel;
  * run-report metadata (the BENCH_*.json provenance block);
  * ``maybe_trace`` env gating (``ForecastServer``'s telemetry is
    covered in ``test_serve_forecast.py``);
  * the full instrumented stack on 8 fake devices (subprocess, multidev):
    measured collective bytes == per-field model with metrics on.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import (
    DriftResult,
    MATCH_KEYS,
    MetricsRegistry,
    RunReport,
    check_drift,
    maybe_trace,
    metrics,
    runtime_metadata,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _metrics_off():
    """Every test starts and ends with metrics disabled (the default)."""
    prev = metrics.current()
    metrics.disable()
    yield
    if prev is not None:
        metrics.enable(prev)
    else:
        metrics.disable()


# --- registry core --------------------------------------------------------


def test_counters_gauges_and_snapshot_roundtrip():
    reg = MetricsRegistry()
    assert reg.inc("a") == 1.0
    assert reg.inc("a", 2.5) == 3.5
    reg.set_gauge("g", 7)
    with reg.timer("t"):
        pass
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 3.5}
    assert snap["gauges"] == {"g": 7.0}
    assert snap["timers"]["t"]["count"] == 1
    json.dumps(snap)  # must be JSON-serialisable as-is


def test_timer_nesting_records_joined_path():
    reg = MetricsRegistry()
    with reg.timer("outer"):
        with reg.timer("inner"):
            pass
        with reg.timer("inner"):
            pass
    assert sorted(reg.timers) == ["outer", "outer/inner"]
    assert reg.timers["outer/inner"].count == 2
    assert reg.timers["outer"].count == 1
    assert reg.timers["outer"].total_s >= reg.timers["outer/inner"].total_s


def test_observe_records_external_duration():
    reg = MetricsRegistry()
    reg.observe("lat", 0.25)
    reg.observe("lat", 0.75)
    stat = reg.timers["lat"].as_dict()
    assert stat["count"] == 2
    assert stat["min_s"] == 0.25
    assert stat["max_s"] == 0.75
    assert stat["mean_s"] == 0.5


def test_reset_clears_everything():
    reg = MetricsRegistry()
    reg.inc("c")
    reg.set_gauge("g", 1)
    with reg.timer("t"):
        pass
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "timers": {}}


# --- disabled path: zero overhead -----------------------------------------


def test_disabled_hooks_are_noops():
    assert metrics.current() is None
    metrics.inc("never")
    metrics.set_gauge("never", 1)
    metrics.observe("never", 1.0)
    # The disabled timer is the SHARED null singleton — no allocation.
    t1, t2 = metrics.timer("a"), metrics.timer("b")
    assert t1 is t2 is metrics._NULL_TIMER
    with t1:
        pass
    # Enabling afterwards starts empty: nothing leaked through.
    reg = metrics.enable()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "timers": {}}


def test_using_scopes_and_restores():
    with metrics.using() as reg:
        assert metrics.current() is reg
        metrics.inc("x")
        assert reg.counters["x"] == 1.0
    assert metrics.current() is None


# --- instrument_call ------------------------------------------------------


def test_instrument_call_records_when_enabled():
    fn = metrics.instrument_call(lambda a: a + 1, "test.fn")
    assert fn(1) == 2  # disabled: pure passthrough, nothing recorded
    with metrics.using() as reg:
        assert fn(jnp.float32(2)) == 3
        assert fn(jnp.float32(3)) == 4
        assert reg.counters["test.fn.calls"] == 2.0
        assert reg.timers["test.fn"].count == 2
    assert fn.metric_name == "test.fn"


def test_instrument_call_steps_aside_under_trace():
    fn = metrics.instrument_call(lambda a: a * 2, "test.traced")
    with metrics.using() as reg:
        out = jax.jit(fn)(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(out), [0.0, 2.0, 4.0, 6.0])
        # Trace-time execution must NOT pollute wall-clock stats.
        assert "test.traced" not in reg.timers
        assert "test.traced.calls" not in reg.counters


# --- drift detector -------------------------------------------------------


def test_drift_inside_tolerance():
    reg = MetricsRegistry()
    d = check_drift("wire", measured=1005, model=1000, tolerance=0.01, registry=reg)
    assert isinstance(d, DriftResult)
    assert d.ok and abs(d.ratio - 1.005) < 1e-12
    assert reg.counters["wire.measured_bytes"] == 1005
    assert reg.counters["wire.model_bytes"] == 1000
    assert reg.gauges["wire.ratio"] == d.ratio
    assert "wire.drift_flags" not in reg.counters


def test_drift_outside_tolerance_flags():
    reg = MetricsRegistry()
    d = check_drift("wire", measured=1100, model=1000, tolerance=0.01, registry=reg)
    assert not d.ok
    assert reg.counters["wire.drift_flags"] == 1.0
    assert "ratio=1.1" in d.describe()


def test_drift_zero_model_edge():
    assert check_drift("z", measured=0, model=0).ok
    assert not check_drift("z", measured=8, model=0).ok


# --- run report / metadata ------------------------------------------------


def test_runtime_metadata_has_match_keys():
    meta = runtime_metadata()
    for key in MATCH_KEYS:
        assert key in meta, meta
    assert meta["backend"] == jax.default_backend()
    assert meta["device_count"] == jax.device_count()
    assert meta["jax_version"] == jax.__version__


def test_run_report_roundtrip(tmp_path):
    rep = RunReport.begin("unit")
    rep.add_section("rows", [{"name": "a", "value": 1.0}])
    with metrics.using() as reg:
        reg.inc("c")
        rep.attach_metrics(reg)
    path = rep.write(tmp_path / "report.json")
    loaded = json.loads(path.read_text())
    assert loaded["name"] == "unit"
    assert loaded["sections"]["rows"][0]["value"] == 1.0
    assert loaded["metrics"]["counters"] == {"c": 1.0}
    assert all(k in loaded["metadata"] for k in MATCH_KEYS)


# --- profiler hooks -------------------------------------------------------


def test_maybe_trace_noop_without_env(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE_DIR", raising=False)
    with maybe_trace("label") as d:
        assert d is None


def test_maybe_trace_captures_into_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE_DIR", str(tmp_path))
    with maybe_trace("unit"):
        jax.block_until_ready(jnp.arange(8.0) * 2)
    # Degrades to a no-op on profiler failure, but the label dir must exist.
    assert (tmp_path / "unit").is_dir()


# --- the instrumented stack on 8 fake devices -----------------------------


@pytest.mark.multidev
def test_obs_instrumented_stack_8dev():
    """REPRO_METRICS=1 auto-enables in the child; measured collective bytes
    match the per-field model (ratio exactly 1.0 in practice) and the
    instrumented results bit-match the uninstrumented ones."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_METRICS"] = "1"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / "_obs_check.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL_OK" in proc.stdout
