"""The trace reduction and the byte counts it rests on: checked on small
hand-made traces whose answers are known, and on a small trace recorded on
a TPU v5e (``data/recorded_trace.json``)."""

import json
import os
import types

import pytest

from bench import harness, hlo, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
F32 = "f32[8,16,128]{2,1,0:T(8,128)}"
ARRAY = 8 * 16 * 128 * 4


def kernel(name, operand):
    return (f"%{name} = {F32} custom-call({F32} %{operand}, {F32} %{operand}, "
            f"{F32} %{operand}, s32[1,4]{{1,0}} %meta), "
            'custom_call_target="tpu_custom_call"')


PAD = f"%pad.1 = {F32} pad({F32} %x, f32[] %c), padding=0_0x0_8x0_0"
PERMUTE = (f"%collective-permute-start = ({F32}, {F32}, u32[], u32[]) "
           f"collective-permute-start({F32} %slice.2), source_target_pairs={{{{0,1}}}}")
PERMUTE_DONE = f"%collective-permute-done = {F32} collective-permute-done(({F32}, {F32}) %c)"
WHILE = f"%while = (s32[], {F32}) while((s32[], {F32}) %tuple), condition=%cond, body=%body"


def trace(device_events, window=(0, 1000), host=()):
    return {"devices": {"TPU:0": [list(e) for e in device_events]},
            "host": [["bench.window", window[0], window[1] - window[0]], *map(list, host)]}


def test_distinct_buffer_bytes_count_a_repeated_operand_once():
    # Three slabs of one array and the 16-byte meta: one input, one output.
    assert hlo.distinct_buffer_bytes(kernel("k", "x")) == 2 * ARRAY + 16
    assert hlo.kind(kernel("k", "x")) == "kernel"
    assert hlo.kind(PAD) == "glue"
    assert hlo.kind(WHILE) == "container"
    assert hlo.kind(PERMUTE) == hlo.kind(PERMUTE_DONE) == "collective"
    assert hlo.result_bytes(PERMUTE) == ARRAY


def test_busy_is_the_union_and_containers_hold_no_time_of_their_own():
    r = tracing.reduce(trace([
        (WHILE, 100, 600),            # spans the two ops below and a gap
        (kernel("k", "x"), 100, 200),
        (PAD, 500, 100),
        (PAD, 650, 100),              # overlaps the loop's end
    ]))
    d = r["devices"]["TPU:0"]
    assert d["busy_s"] == pytest.approx(650e-9)      # [100, 750)
    assert d["kernel_s"] == pytest.approx(200e-9)
    assert d["glue_s"] == pytest.approx(200e-9)
    assert d["collective_s"] == 0
    assert r["window_s"] == pytest.approx(1000e-9)


def test_window_clips_ops_and_their_bytes():
    r = tracing.reduce(trace([(kernel("k", "x"), -100, 200), (PAD, 900, 200)]))
    d = r["devices"]["TPU:0"]
    assert d["kernel_s"] == pytest.approx(100e-9)
    assert d["kernel_bytes"] == pytest.approx((2 * ARRAY + 16) / 2)
    assert d["glue_s"] == pytest.approx(100e-9)


def test_exposed_collective_is_the_part_no_compute_covers():
    r = tracing.reduce(trace([
        (PERMUTE, 0, 300),            # covered by the kernel for 200 of its 300
        (kernel("k", "x"), 100, 400),
        (PERMUTE_DONE, 500, 100),     # nothing else runs: all exposed
    ]))
    d = r["devices"]["TPU:0"]
    assert d["collective_s"] == pytest.approx(400e-9)
    assert d["exposed_collective_s"] == pytest.approx(200e-9)
    assert d["collective_bytes"] == ARRAY     # the -done is not counted again


def test_idle_gaps_go_to_the_host_span_they_fall_in():
    r = tracing.reduce(trace(
        [(kernel("k", "x"), 0, 400), (PAD, 700, 100)],
        host=[("bench.output_copy", 350, 400), ("bench.call", 800, 150)]))
    gaps = dict(r["idle_gaps"])
    assert gaps["host:bench.output_copy"] == pytest.approx(300e-9)   # [400, 700)
    assert gaps["host:bench.call"] == pytest.approx(150e-9)          # [800, 950)
    assert gaps["host:none"] == pytest.approx(50e-9)                 # [950, 1000)
    run = types.SimpleNamespace(trace=r)
    assert harness.load_module(
        os.path.join(harness.BENCH, "metrics", "device.idle_share.forecast.py"),
        "idle").read(run) == pytest.approx(50.0)


def test_a_window_span_is_required():
    with pytest.raises(ValueError):
        tracing.reduce({"devices": {}, "host": []})


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_a_sound_hbm_share(recorded):
    r = tracing.reduce(recorded["events"])
    d = r["devices"]["TPU:0"]
    assert 0 < d["busy_s"] <= r["window_s"]
    assert d["kernel_s"] > 0 and d["glue_s"] > 0 and d["collective_s"] == 0
    assert d["kernel_s"] + d["glue_s"] <= d["busy_s"] * (1 + 1e-9)
    run = types.SimpleNamespace(trace=r, window=recorded["window"],
                                peaks=harness.peaks_for(recorded["kind"], harness.BENCH))
    share = harness.load_module(
        os.path.join(harness.BENCH, "metrics", "kernel.hbm_share.py"), "share").read(run)
    assert 0 < share <= 100
    # Counting each of the three slab operands would read three inputs.
    kernels = [e for e in recorded["events"]["devices"]["TPU:0"] if hlo.is_kernel(e[0])]
    assert all(hlo.distinct_buffer_bytes(e[0]) < hlo.shape_bytes(e[0].split(", custom_call_target")[0])
               for e in kernels)
