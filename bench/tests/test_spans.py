"""The serving layer's span readers: on hand-built span rings whose answers
are known, and on a tiny served run on the CPU."""

import math
import os
import types

import pytest

from bench import harness, spans
from repro.obs import events

READERS = ("served.host_ms_per_batch", "served.device_wait_ms_per_batch",
           "served.stack_ms_per_batch", "served.dispatch_ms_per_batch",
           "served.unstack_ms_per_batch", "served.queue_wait_p95_ms")
MS = 1_000_000


def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics", f"{name}.py"),
                               f"bench_metric_{name}")


def read_all(run):
    return {name: reader(name).read(run) for name in READERS}


def _span(name, start, end, sid, parent=None, rid=None, batch=None):
    sp = events.Span(name, rid, batch)
    sp.start_ns, sp.end_ns, sp.id, sp.parent = start, end, sid, parent
    return sp


def server_ring(n_batches, members=2, scale=1):
    """The ring of ``n_batches`` server batches of ``members`` requests, in
    the order the server records it; batch ``b``'s phases take 1..6 ms
    times ``scale * (b + 1)``, its step 1 ms more, and its requests wait
    ``b`` and ``b + 1`` ms."""
    ring, sid, t = [], 0, 0
    for b in range(n_batches):
        step_id, start = sid, t
        sid += 1
        k = scale * (b + 1)
        for j in range(members):
            ring.append(_span(spans.QUEUE_WAIT, t - (b + j) * MS, t, sid, rid=sid, batch=b))
            sid += 1
        for i, phase in enumerate(spans.PHASES, 1):
            ring.append(_span(f"serve.forecast.{phase}", t, t + i * k * MS, sid, step_id,
                              batch=b))
            t += i * k * MS
            sid += 1
        t += MS
        ring.append(_span(spans.STEP, start, t, step_id, batch=b))
    return ring


def window(batches, members):
    return types.SimpleNamespace(window={"counts": {"batches": batches, "members": members}})


@pytest.fixture
def ring(monkeypatch):
    def use(spans_list):
        monkeypatch.setattr(events, "spans", lambda: list(spans_list))
    return use


def test_readers_take_the_last_batches_of_the_ring(ring):
    ring(server_ring(5))
    got = read_all(window(2, 4))       # batches 3 and 4: k = 4 and 5
    assert got["served.device_wait_ms_per_batch"] == pytest.approx(4 * 4.5)
    assert got["served.stack_ms_per_batch"] == pytest.approx(2 * 4.5)
    assert got["served.dispatch_ms_per_batch"] == pytest.approx(3 * 4.5)
    assert got["served.unstack_ms_per_batch"] == pytest.approx(5 * 4.5)
    # The step: 21 k ms of phases and 1 ms between them; less its wait.
    assert got["served.host_ms_per_batch"] == pytest.approx(17 * 4.5 + 1)
    # The last four waits: 3, 4, 4, 5 ms.
    assert got["served.queue_wait_p95_ms"] == pytest.approx(4.85)


def test_host_and_wait_make_the_step(ring):
    ring(server_ring(3, scale=7))
    run = window(3, 6)
    rows = spans.batches(run)
    step_ms = 1e3 * sum(r["step"] for r in rows) / len(rows)
    got = read_all(run)
    assert (got["served.host_ms_per_batch"] + got["served.device_wait_ms_per_batch"]
            == pytest.approx(step_ms))


def test_a_ring_short_of_the_window_reads_nothing(ring):
    full = server_ring(4)
    ring(full[-3 * 9:])                # the first batch fell off the ring
    assert set(read_all(window(4, 8)).values()) == {None}
    ring(full)
    assert None not in read_all(window(4, 8)).values()


def test_a_batch_that_lost_a_phase_reads_nothing(ring):
    full = server_ring(3)
    ring(full[3:])                     # the first batch's admit fell off
    got = read_all(window(3, 4))
    assert got["served.stack_ms_per_batch"] is None
    assert got["served.host_ms_per_batch"] is None
    assert got["served.queue_wait_p95_ms"] is not None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    monkeypatch.delattr(events, "spans")
    assert set(read_all(window(3, 6)).values()) == {None}


def test_only_the_served_cell_lists_the_readers():
    served = {m["name"] for m in harness.find_cell("sparta_ens21.served").per_layer}
    forecast = {m["name"] for m in harness.find_cell("cosmo1_hdiff.forecast").per_layer}
    assert set(READERS) <= served
    assert not set(READERS) & forecast


def test_readers_on_a_tiny_served_run(tiny_root):
    cell = harness.find_cell("sparta_ens21.served", tiny_root)
    mod = harness.load_module(f"{cell.bench}/drivers/served.py", "bench_driver_spans")
    driver = mod.Driver(cell.config, cell.traffic, 2**31 + 13)
    driver.warm()
    record = driver.window(0.3)
    run = harness.Run(setup_s=0.0, window=record, trace=None, peaks={})
    got = read_all(run)
    assert all(v is not None and math.isfinite(v) and v >= 0 for v in got.values()), got
    rows = spans.batches(run)
    assert len(rows) == record["counts"]["batches"]
    step_ms = 1e3 * sum(r["step"] for r in rows) / len(rows)
    assert (got["served.host_ms_per_batch"] + got["served.device_wait_ms_per_batch"]
            == pytest.approx(step_ms))
    # The phases lie inside their step.
    assert all(sum(r[p] for p in spans.PHASES) <= r["step"] for r in rows)
