"""repro.obs.events spans: the one timing primitive of the instrumented
layers.

  * every span has its own id, the id of the span open around it, and
    start/end stamps nested inside its parent's;
  * the ring is bounded, and what falls off is counted;
  * a span recorded after the fact from two stamps has no parent;
  * with a metrics registry a span is a timer of its name, with a flight
    recorder one event of its name; with neither the ring still fills;
  * a ``with`` span is a ``jax.profiler.TraceAnnotation``: a CPU profiler
    capture holds the span names on its ``/host:CPU`` plane.
"""

import glob
import os
from collections import deque

import pytest

import jax

from repro.obs import events, metrics


@pytest.fixture(autouse=True)
def _obs_off():
    prev_reg, prev_rec = metrics.current(), events.current()
    metrics.disable()
    events.disable()
    yield
    metrics.enable(prev_reg) if prev_reg is not None else metrics.disable()
    events.enable(prev_rec) if prev_rec is not None else events.disable()


def _new_spans(since):
    """The spans in the ring with an id above ``since``, by id."""
    return sorted((s for s in events.spans() if s.id > since), key=lambda s: s.id)


def _last_id():
    return max((s.id for s in events.spans()), default=-1)


def test_spans_nest_by_parent_id():
    before = _last_id()
    with events.span("outer", batch=4) as outer:
        with events.span("inner", rid=7) as inner:
            pass
        with events.span("second"):
            with events.span("leaf"):
                pass
    got = {s.name: s for s in _new_spans(before)}
    assert set(got) == {"outer", "inner", "second", "leaf"}
    assert got["outer"] is outer and got["inner"] is inner
    assert got["outer"].parent is None
    assert got["inner"].parent == got["second"].parent == got["outer"].id
    assert got["leaf"].parent == got["second"].id
    assert len({s.id for s in got.values()}) == 4
    assert (got["inner"].rid, got["inner"].batch) == (7, None)
    assert (got["outer"].rid, got["outer"].batch) == (None, 4)
    for child, parent in (("inner", "outer"), ("second", "outer"), ("leaf", "second")):
        c, p = got[child], got[parent]
        assert p.start_ns <= c.start_ns <= c.end_ns <= p.end_ns
    assert got["inner"].end_ns <= got["second"].start_ns
    # Children finish first, so the ring holds them before their parent.
    ring = [s.name for s in events.spans() if s.id > before]
    assert ring == ["inner", "leaf", "second", "outer"]


def test_tags_set_inside_the_block_are_recorded():
    with events.span("work") as sp:
        sp.rid = 11
    assert events.spans()[-1].rid == 11


def test_a_raising_block_still_records_and_unwinds():
    with pytest.raises(RuntimeError):
        with events.span("fails"):
            raise RuntimeError("boom")
    assert events.spans()[-1].name == "fails"
    with events.span("after") as after:
        pass
    assert after.parent is None


def test_ring_is_bounded_and_counts_drops(monkeypatch):
    monkeypatch.setattr(events, "_SPANS", deque(maxlen=4))
    dropped = events.spans_dropped()
    for i in range(6):
        with events.span("tick", batch=i):
            pass
    assert [s.batch for s in events.spans()] == [2, 3, 4, 5]
    assert events.spans_dropped() - dropped == 2


def test_after_the_fact_span_has_its_stamps_and_no_parent():
    with events.span("open") as open_span:
        sp = events.record_span("queue_wait", 1_000, 4_000, rid=3, batch=9)
    assert (sp.start_ns, sp.end_ns, sp.rid, sp.batch) == (1_000, 4_000, 3, 9)
    assert sp.parent is None
    assert sp.duration_s == pytest.approx(3e-6)
    assert sp.id != open_span.id
    assert events.spans()[-2] is sp


def test_registry_observes_each_span_under_its_name():
    with metrics.using() as reg:
        with events.span("phase"):
            with events.span("phase.part"):
                pass
        events.record_span("wait", 0, 2_000_000)
    assert reg.timers["phase"].count == reg.timers["phase.part"].count == 1
    assert reg.timers["wait"].total_s == pytest.approx(2e-3)
    assert reg.timers["phase"].total_s >= reg.timers["phase.part"].total_s


def test_recorder_gets_one_event_per_span():
    with events.using() as rec:
        with events.span("phase", batch=2, label="x") as sp:
            sp.data["late"] = 1
        events.record_span("wait", 0, 1_000, rid=5)
    (ev,) = rec.events("phase")
    assert ev.data == {"duration_s": sp.duration_s, "batch": 2, "label": "x", "late": 1}
    (wait,) = rec.events("wait")
    assert wait.data == {"duration_s": pytest.approx(1e-6), "rid": 5}


def test_spans_fill_the_ring_with_nothing_installed():
    assert metrics.current() is None and events.current() is None
    with events.span("bare") as sp:
        pass
    assert events.spans()[-1] is sp and sp.end_ns >= sp.start_ns


def test_profiler_capture_holds_span_names_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with events.span("serve.forecast.step", batch=0):
            with events.span("serve.forecast.dispatch"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
        events.record_span("serve.forecast.queue_wait", 0, 1)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    names = {e.name for line in host.lines for e in line.events}
    assert {"serve.forecast.step", "serve.forecast.dispatch"} <= names
    # An after-the-fact span is not annotated.
    assert "serve.forecast.queue_wait" not in names
    step = next(e for line in host.lines for e in line.events
                if e.name == "serve.forecast.step")
    disp = next(e for line in host.lines for e in line.events
                if e.name == "serve.forecast.dispatch")
    assert step.start_ns <= disp.start_ns
    assert disp.start_ns + disp.duration_ns <= step.start_ns + step.duration_ns
