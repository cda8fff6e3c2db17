"""repro.obs.events + repro.obs.export: flight recorder and the
Prometheus exposition.

Covers the event-log contracts:

  * the ring is bounded (oldest events dropped + counted), filterable, and
    ordered by a recorder-local sequence number;
  * with a sink every event lands in the JSONL file as recorded, behind a
    ``meta`` header line carrying the runtime stamp;
  * a span records one event with the measured duration on the active
    recorder (the span primitive itself: ``test_obs_spans.py``);
  * ``crash_dump`` flushes the whole ring (+ reason + metadata) to a JSON
    document, defaulting next to the sink;
  * the module switchboard mirrors ``repro.obs.metrics`` exactly —
    zero-overhead no-ops when disabled, env auto-enable via
    ``REPRO_EVENT_LOG``;
  * ``prometheus_text`` renders counters/gauges/timers in the exposition
    format (sanitised names, ``_total`` counters, timer summaries);
  * ``ForecastServer`` records request-lifecycle events
    (submit/queue_wait/step/retire) and serves the exposition via
    ``metrics_text()``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.obs import events, metrics
from repro.obs.events import EVENT_LOG_ENV, FlightRecorder
from repro.obs.export import prometheus_text, sanitize_metric_name

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _obs_off():
    prev_reg, prev_rec = metrics.current(), events.current()
    metrics.disable()
    events.disable()
    yield
    metrics.enable(prev_reg) if prev_reg is not None else metrics.disable()
    events.enable(prev_rec) if prev_rec is not None else events.disable()


# --- ring semantics -------------------------------------------------------


def test_ring_is_bounded_and_counts_drops():
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record("tick", i=i)
    assert len(rec) == 3
    assert rec.dropped == 2
    assert [e.data["i"] for e in rec.events()] == [2, 3, 4]
    # Sequence numbers keep the total order even after drops.
    assert [e.seq for e in rec.events()] == [2, 3, 4]


def test_events_filter_by_kind():
    rec = FlightRecorder()
    rec.record("a", n=1)
    rec.record("b", n=2)
    rec.record("a", n=3)
    assert [e.data["n"] for e in rec.events("a")] == [1, 3]
    assert rec.events("missing") == []


def test_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_span_records_one_event_with_duration():
    with events.using() as rec:
        with events.span("phase", label="x"):
            pass
    (ev,) = rec.events("phase")
    assert ev.data["label"] == "x"
    assert ev.data["duration_s"] >= 0.0


# --- JSONL sink + crash dump ----------------------------------------------


def test_sink_writes_meta_header_then_events(tmp_path):
    sink = tmp_path / "run" / "events.jsonl"  # parent dir auto-created
    rec = FlightRecorder(sink=sink)
    rec.record("alpha", v=1)
    rec.record("beta", v=2)
    rec.close()
    lines = [json.loads(l) for l in sink.read_text().splitlines()]
    assert lines[0]["kind"] == "meta" and lines[0]["seq"] == -1
    assert "jax_version" in lines[0]["data"]
    assert [l["kind"] for l in lines[1:]] == ["alpha", "beta"]
    assert lines[1]["data"] == {"v": 1}


def test_crash_dump_defaults_next_to_sink(tmp_path):
    sink = tmp_path / "events.jsonl"
    rec = FlightRecorder(capacity=2, sink=sink)
    for i in range(3):
        rec.record("step", i=i)
    out = rec.crash_dump(reason="blew up")
    assert out == tmp_path / "events.jsonl.crash.json"
    dump = json.loads(out.read_text())
    assert dump["reason"] == "blew up"
    assert dump["dropped"] == 1
    assert [e["data"]["i"] for e in dump["events"]] == [1, 2]


def test_crash_dump_explicit_path_and_sinkless_noop(tmp_path):
    rec = FlightRecorder()
    rec.record("x")
    assert rec.crash_dump() is None  # no sink, no path: in-memory only
    out = rec.crash_dump(tmp_path / "dump.json", reason="r")
    assert json.loads(out.read_text())["events"][0]["kind"] == "x"


# --- switchboard ----------------------------------------------------------


def test_disabled_hooks_are_noops():
    assert events.current() is None
    assert events.record("never", x=1) is None
    with events.span("never"):
        pass
    assert events.crash_dump(reason="never") is None


def test_using_scopes_and_restores():
    with events.using() as rec:
        assert events.current() is rec
        events.record("inside")
        assert len(rec) == 1
    assert events.current() is None


def test_enable_disable_roundtrip():
    rec = events.enable(FlightRecorder(capacity=8))
    assert events.enabled() and events.current() is rec
    events.disable()
    assert not events.enabled()


def test_disable_closes_sink_and_reenable_reopens_without_second_header(tmp_path):
    """The switchboard owns the fd of whatever it installed: disable()
    must close it (no leak across repeated scopes), and re-enabling the
    same recorder lazily reopens the sink WITHOUT duplicating the meta
    header."""
    sink = tmp_path / "e.jsonl"
    rec = events.enable(FlightRecorder(sink=sink))
    events.record("a")
    assert rec._file is not None
    events.disable()
    assert rec._file is None  # handle released
    events.enable(rec)
    events.record("b")
    events.disable()
    kinds = [json.loads(l)["kind"] for l in sink.read_text().splitlines()]
    assert kinds == ["meta", "a", "b"]


def test_using_closes_scoped_recorder_sink(tmp_path):
    with events.using(FlightRecorder(sink=tmp_path / "s.jsonl")) as rec:
        events.record("inside")
    assert rec._file is None   # fd released on scope exit...
    assert len(rec) == 1       # ...ring still inspectable


def test_enable_replacement_closes_previous_recorder(tmp_path):
    prev = events.enable(FlightRecorder(sink=tmp_path / "a.jsonl"))
    events.record("x")
    assert prev._file is not None
    events.enable(FlightRecorder())  # replaces prev -> closes its sink
    assert prev._file is None
    events.disable()


def test_env_auto_enable_in_subprocess(tmp_path):
    """REPRO_EVENT_LOG=path installs a sink-backed recorder at import."""
    sink = tmp_path / "auto.jsonl"
    code = (
        "from repro.obs import events\n"
        "assert events.enabled()\n"
        "events.record('auto.test', ok=True)\n"
    )
    env = dict(os.environ)
    env[EVENT_LOG_ENV] = str(sink)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    kinds = [json.loads(l)["kind"] for l in sink.read_text().splitlines()]
    assert kinds == ["meta", "auto.test"]


# --- prometheus exposition ------------------------------------------------


def test_sanitize_metric_name():
    assert sanitize_metric_name("serve.decode_step") == "serve_decode_step"
    assert sanitize_metric_name("a-b c/d") == "a_b_c_d"
    assert sanitize_metric_name("9lives") == "_9lives"


def test_prometheus_text_disabled_is_one_comment_line():
    assert metrics.current() is None
    text = prometheus_text()
    assert text.startswith("#") and text.endswith("\n")


def test_prometheus_text_renders_all_metric_kinds():
    reg = metrics.MetricsRegistry()
    reg.inc("serve.prefills", 3)
    reg.set_gauge("health.psi.nan_count", 0)
    reg.observe("serve.decode_step", 0.25)
    reg.observe("serve.decode_step", 0.75)
    text = prometheus_text(reg)
    assert "repro_serve_prefills_total 3.0" in text
    assert "# TYPE repro_serve_prefills_total counter" in text
    assert "repro_health_psi_nan_count 0.0" in text
    assert "# TYPE repro_serve_decode_step_seconds summary" in text
    assert "repro_serve_decode_step_seconds_count 2" in text
    assert "repro_serve_decode_step_seconds_sum 1.0" in text
    assert "repro_serve_decode_step_seconds_min 0.25" in text
    assert "repro_serve_decode_step_seconds_max 0.75" in text


def test_prometheus_text_accepts_snapshot_and_formats_nonfinite():
    snap = {"counters": {}, "gauges": {"g.nan": float("nan"),
                                       "g.inf": float("inf")}, "timers": {}}
    text = prometheus_text(snap, prefix="x")
    assert "x_g_nan NaN" in text
    assert "x_g_inf +Inf" in text


def test_prometheus_text_uses_active_registry():
    with metrics.using() as reg:
        reg.inc("live.counter")
        assert "repro_live_counter_total 1.0" in prometheus_text()


# --- ForecastServer lifecycle events + exposition -------------------------


def _hdiff_member(rng):
    return jnp.asarray(rng.standard_normal((2, 16, 16)).astype(np.float32))


def test_forecast_server_lifecycle_events_and_metrics_text():
    from repro.ir import hdiff_program
    from repro.serve import ForecastServer

    rng = np.random.default_rng(0)
    with metrics.using(), events.using() as rec:
        srv = ForecastServer(max_batch=4)
        for _ in range(2):
            srv.submit(hdiff_program(), _hdiff_member(rng))
        done = srv.run_until_idle()
        text = srv.metrics_text()
    assert len(done) == 2
    kinds = [e.kind for e in rec.events()]
    assert kinds.count("serve.forecast.submit") == 2
    assert kinds.count("serve.forecast.queue_wait") == 2
    assert kinds.count("serve.forecast.step") == 1
    # One retire record per request (the retire phase span carries no rid).
    retires = [e for e in rec.events("serve.forecast.retire") if "rid" in e.data]
    assert [e.data["rid"] for e in retires] == [r.rid for r in done]
    for e in retires:
        assert e.data["members"] == 2 and e.data["failed"] is False
        assert e.data["items_per_sec"] > 0
    # The server's scrape body is the live registry's exposition.
    assert "repro_serve_forecast_requests_submitted_total 2.0" in text
    assert "repro_serve_forecast_completed_total 2.0" in text
    assert "repro_serve_forecast_step_seconds_count 1.0" in text


def test_forecast_server_metrics_text_without_registry():
    from repro.serve import ForecastServer

    assert metrics.current() is None
    srv = ForecastServer(max_batch=1)
    assert srv.metrics_text().startswith("#")  # well-formed even disabled
