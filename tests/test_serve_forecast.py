"""Forecast-serving stress + fault injection, and the deterministic
compile-cache tests (the jax-level zero-retrace proof lives here, outside
the hypothesis-gated module, so it always runs).

The serving claims under test:

  * interleaved tenants — mixed programs AND mixed grids submitted in one
    arrival order — ALL complete, each batch stays homogeneous, and every
    completed result matches an unbatched oracle (same backend, bit-exact;
    reference oracle, 1e-6);
  * per-request telemetry (queue latency, items/sec) and the member
    occupancy gauge (incl. idle reset) are stamped, and every batch is a
    ``serve.forecast.step`` span over its six phase spans;
  * a NaN-injected request (caught post-step by a HealthMonitor) fails
    ALONE: its batchmates complete with results identical to a run where
    the poisoned request never existed;
  * warm serving never re-traces: a second wave of same-shaped requests is
    all cache hits with zero new jax traces (the acceptance invariant);
  * a batch is one compiled call (member stack, batched lowering,
    per-member slices), built once per cache entry and bit-identical to
    slicing the cache entry's own batched output, whose ``fn`` still
    lowers from a stacked mapping.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conformance import RAGGED_BUDGET, assert_close, assert_equal, ragged_builds, to_host
from repro.ir import (
    hdiff_program,
    laplacian_program,
    lower_reference,
    repeat,
    shallow_water_program,
)
from repro.obs import events, metrics
from repro.obs.health import HealthMonitor, NumericsError
from repro.serve import CompileCache, ForecastServer, compile_key

REPO = Path(__file__).resolve().parents[1]
SEED = 99
PHASES = ("admit", "stack", "dispatch", "device_wait", "unstack", "retire")


@pytest.fixture(autouse=True)
def _obs_on():
    with metrics.using(), events.using():
        yield


def _noise(grid, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(grid).astype(np.float32))


def _sw_fields(grid, seed):
    rng = np.random.default_rng(seed)
    return {
        f: jnp.asarray(rng.standard_normal(grid).astype(np.float32))
        for f in shallow_water_program().inputs
    }


# -- deterministic cache tests (real builder, real jax traces) ---------------


def test_cache_hit_performs_zero_retraces():
    """The acceptance-gate invariant: the per-entry probe counts ACTUAL jax
    traces of the cached callable, and a warm cache serving the same
    (program, grid, dtype, backend, batch) key again — on both the batched
    and unbatched paths — never traces again."""
    p = hdiff_program()
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.standard_normal((3, 2, 16, 16)), jnp.float32)

    cache = CompileCache(4)
    fn = cache.get(p, grid=(2, 16, 16), batch=3)
    fn(xb)
    entry = cache.lookup(compile_key(p, grid=(2, 16, 16), batch=3))
    assert entry.traces == 1  # the miss paid exactly one trace

    for _ in range(3):  # warm hits: same key, fresh data
        fn = cache.get(p, grid=(2, 16, 16), batch=3)
        fn(jnp.asarray(rng.standard_normal((3, 2, 16, 16)), jnp.float32))
    assert entry.traces == 1, "cache hit re-traced"
    assert cache.stats()["hits"] == 3

    # The unbatched path holds the same invariant via its own entry.
    f1 = cache.get(p, grid=(2, 16, 16))
    f1(xb[0])
    f1 = cache.get(p, grid=(2, 16, 16))
    f1(xb[1])
    assert cache.lookup(compile_key(p, grid=(2, 16, 16))).traces == 1
    assert cache.total_traces() == 2  # one per live entry, ever


def test_rebuild_after_eviction_retraces_once():
    """Evicting and re-requesting a key is a miss and costs exactly one
    fresh trace — the probe distinguishes that from a hit-path retrace."""
    hd, lap = hdiff_program(), laplacian_program()
    x = jnp.zeros((2, 16, 16), jnp.float32)
    cache = CompileCache(1)
    cache.get(hd, grid=(2, 16, 16))(x)
    cache.get(lap, grid=(2, 16, 16))(x)   # evicts hd
    cache.get(hd, grid=(2, 16, 16))(x)    # miss again
    assert cache.stats() == {
        "hits": 0, "misses": 3, "evictions": 2, "size": 1, "capacity": 1,
    }
    assert cache.lookup(compile_key(hd, grid=(2, 16, 16))).traces == 1


def test_cache_counters_reach_registry():
    snap = None
    cache = CompileCache(1)
    x = jnp.zeros((2, 16, 16), jnp.float32)
    cache.get(hdiff_program(), grid=(2, 16, 16))(x)      # miss
    cache.get(hdiff_program(), grid=(2, 16, 16))(x)      # hit
    cache.get(laplacian_program(), grid=(2, 16, 16))(x)  # miss + evict
    snap = metrics.current().snapshot()["counters"]
    assert snap["cache.hits"] == 1
    assert snap["cache.misses"] == 2
    assert snap["cache.evictions"] == 1


# -- the serving stress suite -------------------------------------------------


def _submit_interleaved(srv):
    """Three tenants' worth of traffic in one interleaved arrival order:
    hdiff on two DIFFERENT grids (must not co-batch) and shallow_water
    (multi-output) between them. Returns {rid: (program, fields)}."""
    hd, sw = hdiff_program(), shallow_water_program()
    plan = [
        (hd, _noise((2, 16, 16), SEED + 0)),
        (sw, _sw_fields((2, 12, 12), SEED + 1)),
        (hd, _noise((2, 16, 16), SEED + 2)),
        (hd, _noise((2, 24, 24), SEED + 3)),   # other grid: own batch
        (sw, _sw_fields((2, 12, 12), SEED + 4)),
        (hd, _noise((2, 16, 16), SEED + 5)),
        (hd, _noise((2, 16, 16), SEED + 6)),
        (sw, _sw_fields((2, 12, 12), SEED + 7)),
    ]
    subs = {}
    for prog, fields in plan:
        subs[srv.submit(prog, fields)] = (prog, fields)
    return subs


def test_stress_interleaved_tenants_all_complete_and_match_oracles():
    srv = ForecastServer(max_batch=4)
    subs = _submit_interleaved(srv)
    done = srv.run_until_idle()
    assert len(done) == len(subs) and srv.pending() == 0
    assert all(r.done and not r.failed for r in done)
    # Batches stayed homogeneous: 8 requests can't drain in fewer than 3
    # batches (3 distinct group keys), and FIFO grouping gives exactly 3.
    assert srv.stats["batches"] == 3
    assert srv.stats["members"] == len(subs)
    for r in done:
        prog, fields = subs[r.rid]
        want = to_host(lower_reference(prog)(fields))
        assert_close(to_host(r.result), want, err_msg=f"rid={r.rid} vs oracle")


def test_served_results_bit_match_unbatched_same_backend():
    """Same backend, batched through the server vs directly unbatched:
    bit-exact, including for a k=2 composed program."""
    prog = repeat(hdiff_program(), 2)
    fields = [_noise((2, 16, 16), SEED + i) for i in range(3)]
    srv = ForecastServer(max_batch=4)
    rids = [srv.submit(prog, f) for f in fields]
    done = {r.rid: r for r in srv.run_until_idle()}
    base = srv.cache.get(prog, grid=(2, 16, 16))  # the unbatched twin
    for rid, f in zip(rids, fields):
        assert_equal(
            to_host(done[rid].result), to_host(base(f)),
            err_msg=f"rid={rid} batched vs unbatched",
        )


def test_telemetry_stamped_per_request_and_server():
    srv = ForecastServer(max_batch=4)
    _submit_interleaved(srv)
    done = srv.run_until_idle()
    for r in done:
        assert r.queue_latency_s is not None and r.queue_latency_s >= 0
        assert r.items_per_sec is not None and r.items_per_sec > 0
    snap = metrics.current().snapshot()
    # Every batch and every phase of it is a span, a timer of its name.
    batches = srv.stats["batches"]
    for phase in PHASES + ("step",):
        assert snap["timers"][f"serve.forecast.{phase}"]["count"] == batches
    assert snap["timers"]["serve.forecast.step"]["total_s"] > 0
    # Occupancy resets to idle after the drain (the staleness rule).
    assert snap["gauges"]["serve.forecast.member_occupancy"] == 0.0
    assert snap["counters"]["serve.forecast.requests_submitted"] == len(done)
    assert snap["counters"]["serve.forecast.completed"] == len(done)
    assert snap["timers"]["serve.forecast.queue_wait"]["count"] == len(done)
    assert snap["timers"]["serve.forecast.submit"]["count"] == len(done)
    # Retire events carry the per-request telemetry; the retire span adds
    # one event per batch.
    retires = events.current().events("serve.forecast.retire")
    per_request = [e for e in retires if "rid" in e.data]
    assert len(per_request) == len(done)
    assert all(e.data["items_per_sec"] > 0 for e in per_request)
    assert len(retires) - len(per_request) == batches
    submits = events.current().events("serve.forecast.submit")
    assert sorted(e.data["rid"] for e in submits) == sorted(r.rid for r in done)


def test_drain_records_a_step_span_per_batch_with_its_phases():
    srv = ForecastServer(max_batch=2)
    prog = hdiff_program()
    first = max((s.id for s in events.spans()), default=-1)
    rids = [srv.submit(prog, _noise((2, 16, 16), SEED + i)) for i in range(5)]
    done = srv.run_until_idle()
    assert srv.stats["batches"] == 3
    new = [s for s in events.spans() if s.id > first]
    steps = [s for s in new if s.name == "serve.forecast.step"]
    assert [s.batch for s in steps] == [0, 1, 2]
    for step in steps:
        kids = [s for s in new if s.parent == step.id]
        assert [k.name for k in kids] == [f"serve.forecast.{p}" for p in PHASES]
        assert all(k.batch == step.batch for k in kids)
        assert step.start_ns <= kids[0].start_ns
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
        assert kids[-1].end_ns <= step.end_ns
        # The phases are leaves: the batch's call opens no span of its own.
        assert not [s for s in new if s.parent in {k.id for k in kids}]
    # Batches of 2, 2, 1: one call wrapper per batch size, built on the
    # first batch of that size.
    builds = events.current().events("serve.forecast.marshal_build")
    assert [e.data["batch"] for e in builds] == [2, 1]
    # One queue wait per request: submit to dispatch, its rid and batch.
    waits = {s.rid: s for s in new if s.name == "serve.forecast.queue_wait"}
    assert sorted(waits) == sorted(rids)
    submits = [s for s in new if s.name == "serve.forecast.submit"]
    assert sorted(s.rid for s in submits) == sorted(rids)
    by_batch = {s.batch: s for s in steps}
    for r in done:
        w = waits[r.rid]
        assert w.duration_s == r.queue_latency_s
        assert w.start_ns * 1e-9 == pytest.approx(r.submitted_ts, abs=1e-9)
        assert w.end_ns * 1e-9 == pytest.approx(r.dispatch_ts, abs=1e-9)
        admit = next(s for s in new if s.name == "serve.forecast.admit"
                     and s.parent == by_batch[w.batch].id)
        assert admit.start_ns <= w.end_ns <= admit.end_ns
    assert [waits[r].batch for r in rids] == [0, 0, 1, 1, 2]


def test_member_occupancy_gauge_tracks_last_batch():
    srv = ForecastServer(max_batch=4)
    for i in range(3):
        srv.submit(hdiff_program(), _noise((2, 16, 16), SEED + i))
    assert srv.step() is True
    snap = metrics.current().snapshot()
    assert snap["gauges"]["serve.forecast.member_occupancy"] == 3 / 4
    assert srv.step() is False  # idle → gauge drops to 0
    assert metrics.current().snapshot()["gauges"][
        "serve.forecast.member_occupancy"
    ] == 0.0


def test_nan_injected_request_fails_alone():
    """Fault injection: one member's initial conditions carry a NaN. The
    HealthMonitor (abort policy) catches it post-step; that request retires
    with ``error`` set while its batchmates complete with results
    IDENTICAL to a clean run without the poisoned request."""
    clean = [_noise((2, 16, 16), SEED + i) for i in range(3)]
    poisoned = clean[1].at[0, 5, 5].set(jnp.nan)

    srv = ForecastServer(max_batch=4, monitor=HealthMonitor(policy="abort"))
    rid0 = srv.submit(hdiff_program(), clean[0])
    rid_bad = srv.submit(hdiff_program(), poisoned)
    rid2 = srv.submit(hdiff_program(), clean[2])
    done = {r.rid: r for r in srv.run_until_idle()}

    # Three members' fields read and written, the failed member's too; no
    # exchange on one chip.
    assert srv.stats == {"batches": 1, "members": 3, "completed": 2, "failed": 1,
                         "field_bytes": 2 * 3 * 2 * 16 * 16 * 4, "halo_bytes": 0}
    bad = done[rid_bad]
    assert bad.done and bad.failed and bad.result is None
    assert isinstance(bad.error, NumericsError)

    # Batchmates: identical to a server that never saw the poison.
    oracle_srv = ForecastServer(max_batch=4)
    o0 = oracle_srv.submit(hdiff_program(), clean[0])
    o2 = oracle_srv.submit(hdiff_program(), clean[2])
    oracle = {r.rid: r for r in oracle_srv.run_until_idle()}
    assert_equal(to_host(done[rid0].result), to_host(oracle[o0].result))
    assert_equal(to_host(done[rid2].result), to_host(oracle[o2].result))

    snap = metrics.current().snapshot()["counters"]
    assert snap["serve.forecast.failed"] == 1
    fails = events.current().events("serve.forecast.fail")
    assert len(fails) == 1 and fails[0].data["rid"] == rid_bad


def test_nan_isolation_multi_output():
    """Same isolation story for a coupled system: poisoning one member's h
    field fails only that request; surviving members' u/v/h all match."""
    fields = [_sw_fields((2, 12, 12), SEED + i) for i in range(3)]
    bad = dict(fields[0])
    bad["h"] = bad["h"].at[1, 3, 3].set(jnp.inf)

    srv = ForecastServer(max_batch=4, monitor=HealthMonitor(policy="abort"))
    rid_bad = srv.submit(shallow_water_program(), bad)
    rids = [srv.submit(shallow_water_program(), f) for f in fields[1:]]
    done = {r.rid: r for r in srv.run_until_idle()}
    assert done[rid_bad].failed
    ref = lower_reference(shallow_water_program())
    for rid, f in zip(rids, fields[1:]):
        assert not done[rid].failed
        assert_close(to_host(done[rid].result), to_host(ref(f)))


def test_warm_serving_is_all_hits_with_zero_retraces():
    """Two identical waves of traffic: the second wave is 100% cache hits
    and adds ZERO jax traces — the serving-level acceptance invariant."""
    srv = ForecastServer(max_batch=4)

    def wave(seed0):
        for i in range(4):
            srv.submit(hdiff_program(), _noise((2, 16, 16), seed0 + i))
        srv.run_until_idle()

    wave(SEED)
    misses0 = srv.cache.stats()["misses"]
    traces0 = srv.cache.total_traces()
    wave(SEED + 100)  # fresh data, same shapes
    assert srv.cache.stats()["misses"] == misses0, "warm wave missed"
    assert srv.cache.total_traces() == traces0, "warm wave re-traced"
    assert srv.cache.hit_rate > 0


def test_submit_validation():
    srv = ForecastServer()
    with pytest.raises(ValueError, match="pass a mapping"):
        srv.submit(shallow_water_program(), jnp.zeros((2, 8, 8)))
    with pytest.raises(ValueError, match="missing input"):
        srv.submit(shallow_water_program(), {"u": jnp.zeros((2, 8, 8))})
    with pytest.raises(ValueError, match="share a grid"):
        srv.submit(
            shallow_water_program(),
            {"u": jnp.zeros((2, 8, 8)), "v": jnp.zeros((2, 8, 8)),
             "h": jnp.zeros((2, 9, 9))},
        )
    with pytest.raises(ValueError, match="depth, rows, cols"):
        srv.submit(hdiff_program(), jnp.zeros((8, 8)))
    with pytest.raises(ValueError, match="max_batch"):
        ForecastServer(max_batch=0)


# -- the one-call batch path ----------------------------------------------------


def _serve(srv, prog, fields):
    rids = [srv.submit(prog, f) for f in fields]
    done = {r.rid: r for r in srv.run_until_idle()}
    return [done[rid] for rid in rids]


def _stacked(prog, fields):
    return {
        f: jnp.stack([m[f] if isinstance(m, dict) else m for m in fields])
        for f in prog.inputs
    }


# The one-call path on two kinds of grid. ``even``: the reference backend
# on small grids. ``ragged``: the served cells' pallas backend on COSMO-E's
# class of grid (39 rows, 58 lanes) at a VMEM budget that plans an 8-row
# tile whose ``cdiv`` grid leaves a 7-row last tile.
_SERVED_GRIDS = {
    "even": ("reference", {"hdiff": (2, 16, 16), "shallow_water": (2, 12, 12)}, {}),
    "ragged": (
        "pallas",
        {"hdiff": (2, 39, 58), "shallow_water": (2, 39, 58)},
        {"interpret": True, "vmem_budget": RAGGED_BUDGET},
    ),
}


@pytest.mark.parametrize("grid_id", list(_SERVED_GRIDS))
@pytest.mark.parametrize("n", [1, 3, 8])
@pytest.mark.parametrize("name", ["hdiff", "shallow_water"])
def test_one_call_batch_bit_matches_slices_of_the_batched_output(name, n, grid_id):
    """Each member's result from the one-call path equals ``out[i]`` of the
    cache entry's own batched call on the stacked fields, bit for bit —
    for a single-output program and for a multi-output one, on an evenly
    tiled grid and on a ragged one."""
    backend, grids, lower_kwargs = _SERVED_GRIDS[grid_id]
    prog = {"hdiff": hdiff_program(), "shallow_water": shallow_water_program()}[name]
    grid = grids[name]
    make = _noise if name == "hdiff" else _sw_fields
    fields = [make(grid, SEED + i) for i in range(n)]
    srv = ForecastServer(backend=backend, max_batch=8, lower_kwargs=lower_kwargs)
    done = _serve(srv, prog, fields)
    assert srv.stats["batches"] == 1
    assert (ragged_builds(metrics.current()) > 0) == (grid_id == "ragged")
    entry = srv.cache.lookup(compile_key(prog, grid=grid, backend=backend, batch=n))
    out = entry.fn(_stacked(prog, fields))
    for i, r in enumerate(done):
        want = {f: v[i] for f, v in out.items()} if isinstance(out, dict) else out[i]
        assert isinstance(r.result, dict) == isinstance(want, dict)
        assert_equal(to_host(r.result), to_host(want), err_msg=f"member {i} of {n}")
    assert entry.traces == 1  # the wrapper's trace and the direct call share one


def test_marshal_builds_once_per_batch_size():
    """One wrapper per cache entry: batches of 4, 4, 3 build two, and a
    second drain of the same sizes builds none."""
    srv = ForecastServer(max_batch=4)
    prog = hdiff_program()
    fields = [_noise((2, 16, 16), SEED + i) for i in range(11)]
    _serve(srv, prog, fields)
    counters = metrics.current().snapshot()["counters"]
    assert counters["serve.forecast.marshal_builds"] == 2
    _serve(srv, prog, fields[:7])
    counters = metrics.current().snapshot()["counters"]
    assert counters["serve.forecast.marshal_builds"] == 2
    assert srv.stats["batches"] == 5
    builds = events.current().events("serve.forecast.marshal_build")
    assert [(e.data["program"], e.data["batch"]) for e in builds] == [
        ("hdiff", 4), ("hdiff", 3)]
    for n in (4, 3):
        assert srv.cache.lookup(compile_key(prog, grid=(2, 16, 16), batch=n)).marshal


def test_marshal_rebuilds_after_eviction():
    """The wrapper lives on the cache entry: evicting the entry drops it,
    and the next batch of that size builds it again (one more build)."""
    srv = ForecastServer(max_batch=2, cache_capacity=1)
    prog = hdiff_program()
    fields = [_noise((2, 16, 16), SEED + i) for i in range(3)]
    _serve(srv, prog, fields)            # batch of 2, then of 1: evicts 2's
    key2 = compile_key(prog, grid=(2, 16, 16), batch=2)
    assert key2 not in srv.cache
    _serve(srv, prog, fields[:2])        # batch of 2 again: a miss, rebuilt
    counters = metrics.current().snapshot()["counters"]
    assert counters["serve.forecast.marshal_builds"] == 3
    assert srv.cache.stats()["misses"] == 3
    assert srv.cache.lookup(key2).marshal is not None
    assert srv.cache.lookup(key2).traces == 1


def test_cache_entry_fn_still_lowers_from_a_stacked_mapping():
    """The cache entry's ``fn`` keeps its contract: it takes the stacked
    ``{field: (n, D, R, C)}`` mapping, lowers and compiles from it, and its
    batched output slices to the served results."""
    prog = hdiff_program()
    fields = [_noise((2, 16, 16), SEED + i) for i in range(3)]
    srv = ForecastServer(max_batch=4)
    done = _serve(srv, prog, fields)
    entry = srv.cache.lookup(compile_key(prog, grid=(2, 16, 16), batch=3))
    compiled = entry.fn.lower(_stacked(prog, fields)).compile()
    out = compiled(_stacked(prog, fields))
    for i, r in enumerate(done):
        assert_equal(to_host(r.result), to_host(out[i]), err_msg=f"member {i}")
    assert entry.traces == 1


def test_fed_back_results_reuse_the_compiled_call():
    """Closed loop, as an ensemble client runs it: members sliced eagerly
    from one seeded array, then the server's own results fed back. Every
    timestep hits the same compiled signature of each batch size's call."""
    init = jax.jit(lambda k: jax.random.normal(k, (5, 2, 16, 16)))(jax.random.key(SEED))
    states = [init[i] for i in range(5)]
    srv = ForecastServer(max_batch=3)
    prog = hdiff_program()
    for _ in range(3):
        states = [r.result for r in _serve(srv, prog, states)]
    entries = [srv.cache.lookup(k) for k in srv.cache.keys()]
    assert sorted(e.key.batch for e in entries) == [2, 3]
    assert [e.marshal._cache_size() for e in entries] == [1, 1]
    assert srv.cache.total_traces() == 2


@pytest.mark.multidev
def test_served_mesh_results_keep_member_sharding():
    """On a 2x2 mesh the one-call path's per-member results bit-match the
    batched output's slices and keep its (rows, cols) sharding."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / "_served_check.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL_OK" in proc.stdout


@pytest.mark.multidev
def test_served_mesh_fields_match_reference_and_count_the_exchange():
    """The COSMO-1E cell's served path at a ragged size on a 2x2 mesh of
    fake devices: 11 five-field members as sharded-pallas batches of 8 and
    3 match lower_reference and keep their sharding, and the server counts
    each batch's halo exchange (none on one chip)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tests" / "multidev" / "_served_mesh_check.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL_OK" in proc.stdout
