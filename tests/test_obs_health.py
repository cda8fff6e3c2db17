"""repro.obs.health: on-device field stats, the HealthMonitor policies,
and the assimilation SpikeDetector.

Covers the numerics-health contracts:

  * ``field_stats`` counts NaN/Inf exactly and reports finite-only
    min/max/mean/L2 (on-device 0-d arrays; jit-composable);
  * ``HealthMonitor`` probes on cadence only, steps aside under tracers
    (probed jitted steps stay byte-identical), and enforces the three
    policies — ``warn`` keeps running, ``abort`` raises
    :class:`NumericsError`, ``checkpoint-then-abort`` first hands the LAST
    HEALTHY state to ``checkpoint_fn``;
  * probes report through metrics gauges/counters and flight-recorder
    events when those channels are on, and work identically with both off;
  * ``SpikeDetector`` flags non-finite and above-threshold losses through
    the same channels;
  * the mesh-global stats parity + bit-exactness claims on 8 fake devices
    (subprocess, multidev tier).
"""

import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import events, metrics
from repro.obs.health import (
    STAT_KEYS,
    HealthMonitor,
    NumericsError,
    field_stats,
    host_stats,
    is_healthy,
)
from repro.train import SpikeDetector

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with both channels disabled (the default)."""
    prev_reg, prev_rec = metrics.current(), events.current()
    metrics.disable()
    events.disable()
    yield
    metrics.enable(prev_reg) if prev_reg is not None else metrics.disable()
    events.enable(prev_rec) if prev_rec is not None else events.disable()


# --- field_stats ----------------------------------------------------------


def test_field_stats_counts_and_finite_moments():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    x[0, 0] = np.nan
    x[1, 1] = np.inf
    x[2, 2] = -np.inf
    s = host_stats(field_stats(jnp.asarray(x)))
    assert set(s) == set(STAT_KEYS)
    assert s["size"] == 12 and s["nan_count"] == 1 and s["inf_count"] == 2
    finite = x[np.isfinite(x)]
    assert s["min"] == finite.min() and s["max"] == finite.max()
    np.testing.assert_allclose(s["mean"], finite.mean(), rtol=1e-6)
    np.testing.assert_allclose(s["l2"], np.sqrt((finite**2).sum()), rtol=1e-6)


def test_field_stats_all_nonfinite_keeps_counts_as_the_alarm():
    s = host_stats(field_stats(jnp.full((4,), jnp.nan)))
    assert s["nan_count"] == 4
    assert s["mean"] == 0.0 and s["l2"] == 0.0
    assert s["min"] == math.inf and s["max"] == -math.inf
    assert not is_healthy(s)


def test_field_stats_is_jit_safe():
    x = jnp.linspace(-2.0, 2.0, 64).reshape(8, 8)
    jitted = jax.jit(field_stats)
    got, want = host_stats(jitted(x)), host_stats(field_stats(x))
    assert got == want
    # Output leaves are on-device 0-d arrays, not host floats.
    assert all(hasattr(v, "shape") and v.shape == () for v in field_stats(x).values())


def test_field_stats_counts_are_exact_past_float32_precision():
    """Counts accumulate in int32: a field larger than 2^24 elements (where
    float32 integer arithmetic stops being exact) still reports its size —
    and therefore nan/finite counts — exactly."""
    n = 2**24 + 3  # odd excess: not representable in float32
    s = field_stats(jnp.ones((n,), jnp.int8))
    assert s["size"].dtype == jnp.int32
    assert s["nan_count"].dtype == jnp.int32
    assert int(s["size"]) == n
    assert int(s["nan_count"]) == 0 and int(s["inf_count"]) == 0


def test_is_healthy_max_abs_bound():
    s = host_stats(field_stats(jnp.asarray([1.0, -3.0, 2.0])))
    assert is_healthy(s)
    assert is_healthy(s, max_abs=3.0)
    assert not is_healthy(s, max_abs=2.5)


# --- HealthMonitor --------------------------------------------------------


def test_monitor_validates_construction():
    with pytest.raises(ValueError, match="cadence"):
        HealthMonitor(cadence=0)
    with pytest.raises(ValueError, match="policy"):
        HealthMonitor(policy="explode")
    with pytest.raises(ValueError, match="checkpoint_fn"):
        HealthMonitor(policy="checkpoint-then-abort")


def test_monitor_probes_on_cadence_only():
    m = HealthMonitor(cadence=3)
    x = jnp.ones((4,))
    ran = [step for step in range(10) if m.check(step, x) is not None]
    assert ran == [0, 3, 6, 9]
    assert m.probes == 4
    assert m.check(1, x, force=True) is not None  # force overrides cadence
    assert m.last_healthy[0] == 1


def test_monitor_warn_policy_logs_and_continues():
    logged = []
    m = HealthMonitor(cadence=1, policy="warn", log_fn=logged.append)
    bad = jnp.asarray([1.0, jnp.nan])
    stats = m.check(0, bad)
    assert stats["nan_count"] == 1
    assert m.blowups == 1
    assert logged and "blow-up" in logged[0]
    assert m.last_healthy is None  # an unhealthy probe never becomes "healthy"


def test_monitor_abort_policy_raises_with_context():
    m = HealthMonitor(cadence=1, policy="abort", name="psi")
    m.check(0, jnp.ones((3,)))
    with pytest.raises(NumericsError) as ei:
        m.check(1, jnp.asarray([jnp.inf, 0.0]))
    assert ei.value.step == 1 and ei.value.field == "psi"
    assert ei.value.stats["inf_count"] == 1
    assert m.last_healthy[0] == 0


def test_monitor_checkpoint_then_abort_hands_over_last_healthy_state():
    saved = []
    m = HealthMonitor(
        cadence=2, policy="checkpoint-then-abort",
        checkpoint_fn=lambda step, state: saved.append((step, state)),
    )
    good = jnp.arange(4.0)
    m.check(0, good, state={"params": good})
    m.check(2, good * 2, state={"params": good * 2})
    with pytest.raises(NumericsError):
        m.check(4, jnp.asarray([jnp.nan]))
    assert len(saved) == 1
    step, state = saved[0]
    assert step == 2
    np.testing.assert_array_equal(np.asarray(state["params"]), np.arange(4.0) * 2)


def test_monitor_snapshot_state_survives_donated_buffers():
    """A step fn with donate_argnums deletes the buffers a probe retained;
    snapshot_state=True must host-copy last_healthy at probe time so
    checkpoint_fn still reads live arrays after the donation."""
    saved = []
    m = HealthMonitor(
        cadence=1, policy="checkpoint-then-abort", snapshot_state=True,
        checkpoint_fn=lambda s, st: saved.append((s, st)), log_fn=lambda _: None,
    )
    step = jax.jit(lambda p: p * 2.0, donate_argnums=0)
    p = jnp.arange(4.0)
    m.check(0, 1.0, state=p)
    p = step(p)  # donation deletes the retained step-0 buffers
    with pytest.raises(NumericsError):
        m.check(1, float("nan"), state=p)
    ((s, st),) = saved
    assert s == 0
    np.testing.assert_array_equal(np.asarray(st), np.arange(4.0))


def test_monitor_without_snapshot_retains_state_by_reference():
    m = HealthMonitor(cadence=1)
    x = jnp.arange(3.0)
    m.check(0, 1.0, state=x)
    assert m.last_healthy[1] is x  # default: no host copy


def test_monitor_checkpoint_then_abort_without_healthy_probe_still_aborts():
    saved = []
    m = HealthMonitor(
        cadence=1, policy="checkpoint-then-abort",
        checkpoint_fn=lambda s, st: saved.append(s), log_fn=lambda _: None,
    )
    with pytest.raises(NumericsError):
        m.check(0, jnp.asarray([jnp.nan]))
    assert saved == []  # nothing healthy to checkpoint


def test_monitor_steps_aside_under_tracers():
    m = HealthMonitor(cadence=1, policy="abort")

    @jax.jit
    def step(x):
        # Probing a tracer must be a no-op: no probe, no trace pollution.
        assert m.check(0, x) is None
        return x * 2

    bad = jnp.asarray([jnp.nan, 1.0])
    out = step(bad)  # NaN flows through untouched — the probe stepped aside
    assert np.isnan(np.asarray(out)[0])
    assert m.probes == 0


def test_monitor_wrap_probes_outputs_bit_identically():
    calls = []
    m = HealthMonitor(cadence=2, policy="abort", name="out")
    fn = jax.jit(lambda x: x * 1.5)
    wrapped = m.wrap(fn, name="out")
    x = jnp.arange(8.0)
    for _ in range(4):
        calls.append(np.asarray(wrapped(x)))
    assert m.probes == 2  # auto-steps 0 and 2 on cadence 2
    for got in calls:
        np.testing.assert_array_equal(got, np.asarray(fn(x)))


def test_monitor_reports_through_metrics_and_events():
    with metrics.using() as reg, events.using() as rec:
        m = HealthMonitor(cadence=1, policy="warn", name="psi",
                          log_fn=lambda _: None)
        m.check(0, jnp.ones((4,)))
        m.check(1, jnp.asarray([jnp.nan]))
    snap = reg.snapshot()
    assert snap["counters"]["health.probes"] == 2.0
    assert snap["counters"]["health.blowups"] == 1.0
    assert snap["gauges"]["health.psi.nan_count"] == 1.0  # latest probe
    kinds = [e.kind for e in rec.events()]
    assert kinds.count("health.probe") == 2
    assert kinds.count("health.blowup") == 1
    blowup = rec.events("health.blowup")[0]
    assert blowup.data["step"] == 1 and blowup.data["nan_count"] == 1.0


def test_monitor_works_with_both_channels_off():
    assert metrics.current() is None and events.current() is None
    m = HealthMonitor(cadence=1, policy="abort")
    assert m.check(0, jnp.ones((2,)))["nan_count"] == 0
    with pytest.raises(NumericsError):
        m.check(1, jnp.asarray([jnp.inf]))


# --- SpikeDetector --------------------------------------------------------


def _feed_baseline(det, n=8, loss=1.0, start=0):
    for i in range(n):
        assert not det.record(start + i, loss)


def test_spike_detector_flags_above_factor_median():
    det = SpikeDetector(factor=5.0)
    _feed_baseline(det)
    assert det.record(8, 5.1)   # 5.1 > 5.0 * median(1.0)
    assert not det.record(9, 4.9)
    assert det.spikes == [(8, 5.1)]


def test_spike_detector_nonfinite_is_always_a_spike():
    det = SpikeDetector()
    assert det.record(0, float("nan"))  # even during warmup
    assert det.record(1, float("inf"))
    assert len(det.spikes) == 2
    assert det.losses == []  # non-finite never enters the median history


def test_spike_detector_warmup_never_flags_finite_losses():
    det = SpikeDetector(factor=2.0, warmup=5)
    for i, loss in enumerate([100.0, 1.0, 50.0, 2.0, 30.0]):
        assert not det.record(i, loss)


def test_spike_detector_reports_through_metrics_and_events():
    det = SpikeDetector(factor=5.0)
    with metrics.using() as reg, events.using() as rec:
        _feed_baseline(det)
        det.record(8, 99.0)
    assert reg.counters["train.loss_spikes"] == 1.0
    (ev,) = rec.events("train.loss_spike")
    assert ev.data["step"] == 8 and ev.data["loss"] == 99.0
    assert ev.data["threshold"] == 5.0


def test_spike_detector_silent_with_channels_off():
    det = SpikeDetector(factor=5.0)
    _feed_baseline(det)
    assert det.record(8, 99.0)  # still detects; just nothing to report to
    assert det.spikes == [(8, 99.0)]


# --- mesh-global stats + bit-exactness on 8 fake devices ------------------


@pytest.mark.multidev
def test_health_stats_parity_8dev():
    """Sharded field_stats over a 2x4 mesh equals single-device stats to
    1e-6 on the paper grid, and a conformance cell stays bit-exact under
    HealthMonitor.wrap with metrics + flight recorder live."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_METRICS"] = "1"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / "_health_check.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "HEALTH_OK" in proc.stdout
