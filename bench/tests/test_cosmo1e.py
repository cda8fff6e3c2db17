"""The COSMO-1E cell (``cosmo1e_ens11.served_2x2``) driven through whole
runs on four virtual CPU devices, on its own tiny copy of the benchmark
(19 x 29 shards that tile in neither rows nor lanes, five fields, 11
members in batches of 8 and 3): sound, it reads correct and counts the
halo exchange and the per-chip field bytes; with the control (the plain
reference in bfloat16) or a fault in one shard of one member in place of
the timed path, it reads not correct. The mesh readers are checked on a
hand-made four-chip trace."""

import json
import os
import types

import pytest

from bench import harness, tracing
from bench.tests.conftest import copy_bench

CELL = "cosmo1e_ens11.served_2x2"
SEED = 2**31 + 1019
SECONDS = 0.3
TINY = {"depth": 2, "rows": 38, "cols": 58}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("cosmo1e"))
    path = os.path.join(root, "bench", "configs", "cosmo1e_ens11.json")
    with open(path) as f:
        config = json.load(f)
    config.update(TINY)
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def run(root, timed_path=None):
    result, _ = harness.run_cell(CELL, SEED, SECONDS, False, root=root,
                                 require_chip=False, timed_path=timed_path)
    return result


def driver_module(root):
    cell = harness.find_cell(CELL, root)
    return cell, harness.load_module(f"{cell.bench}/drivers/{cell.traffic['driver']}.py",
                                     "bench_driver_cosmo1e")


def test_sound_run_is_correct(root):
    result = run(root)
    assert result["correct"], result["checks"]
    assert result["checks"]["member_rel_err"]["value"] == 0.0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"served_members_per_s", "served_p95_ms", "setup_s"}


def test_window_counts_the_exchange_and_the_chip_bytes(root):
    from repro.dist.halo import call_exchange_bytes

    cell, mod = driver_module(root)
    driver = mod.Driver(cell.config, cell.traffic, SEED)
    driver.warm()
    assert sorted(k.batch for k in driver.server.cache.keys()) == [3, 8]
    for state in driver.states:
        for x in state.values():
            assert len(x.sharding.device_set) == 4 and x.sharding.shard_shape(x.shape) == (
                2, 19, 29)
    counts = driver.window(SECONDS)["counts"]
    grid = (TINY["depth"], TINY["rows"], TINY["cols"])
    per_member = sum(call_exchange_bytes(driver.prog, grid, (1, 2, 2)).values())
    assert counts["halo_bytes"] == counts["members"] * per_member > 0
    field_bytes = counts["members"] * 2 * 5 * 4 * TINY["depth"] * TINY["rows"] * TINY["cols"]
    assert counts["field_bytes"] == field_bytes
    assert counts["chip_field_bytes"] == field_bytes // 4


def test_control_is_not_correct(root):
    cell, mod = driver_module(root)
    result = run(root, mod.control(cell.config, cell.traffic))
    assert not result["correct"], result["checks"]


def _one_shard_altered(fn):
    """One point of one field of the batch's last member moved, inside the
    shard at mesh position (1, 1)."""
    def step(fields):
        out = dict(fn(fields))
        out["t"] = out["t"].at[-1, 0, TINY["rows"] // 2 + 3, TINY["cols"] // 2 + 5].add(1e-2)
        return out
    return step


def test_one_shard_fault_is_not_correct(root):
    result = run(root, _one_shard_altered)
    assert not result["correct"], result["checks"]


PERMUTE = ("%collective-permute-start.1 = (f32[5,6,2,2,29]{4,3,2,1,0}, "
           "f32[5,6,2,2,29]{4,3,2,1,0}, u32[], u32[]) collective-permute-start("
           "f32[5,6,2,2,29]{4,3,2,1,0} %slice.7), source_target_pairs={{0,2},{1,3}}")
PERMUTE_BYTES = 5 * 6 * 2 * 2 * 29 * 4
KERNEL = ('%ir_hdiff_fields.1 = f32[6,2,23,33]{3,2,1,0} custom-call('
          'f32[6,2,23,33]{3,2,1,0} %x), custom_call_target="tpu_custom_call"')


def four_chip_run(counts, busy_ns=(400, 500, 300, 200)):
    """A 1000 ns window on four chips: each runs one kernel of ``busy_ns``
    less 100 ns, then one permute of 100 ns; chip 1 permutes twice."""
    devices = {}
    for i, busy in enumerate(busy_ns):
        events = [[KERNEL, 0, busy - 100], [PERMUTE, busy - 100, 100]]
        if i == 1:
            events.append([PERMUTE, 600, 100])
        devices[f"TPU:{i}"] = events
    trace = tracing.reduce({"devices": devices, "host": [["bench.window", 0, 1000]]})
    return types.SimpleNamespace(trace=trace, window={"counts": counts},
                                 peaks={"hbm_bytes_per_s": 1e9})


def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics", f"{name}.py"), name)


def test_wire_ratio_reads_the_busiest_chips_permutes_over_the_served_model():
    read = reader("halo.wire_ratio.cosmo1e").read
    assert read(four_chip_run({"halo_bytes": 2 * PERMUTE_BYTES})) == pytest.approx(1.0)
    assert read(four_chip_run({"halo_bytes": PERMUTE_BYTES})) == pytest.approx(2.0)
    # A server that counts no exchange (one without the counter) reads nothing.
    assert read(four_chip_run({"halo_bytes": 0})) is None
    assert read(four_chip_run({})) is None


def test_field_hbm_share_reads_per_chip_bytes_over_the_busiest_chip():
    # 350 bytes a chip over chip 1's 600 ns busy (its kernel and two
    # permutes) at 1 GB/s: 350 / 600 of the peak.
    run = four_chip_run({"chip_field_bytes": 350, "field_bytes": 1400})
    assert reader("served.field_hbm_share.cosmo1e").read(run) == pytest.approx(100 * 350 / 600)
    assert reader("served.field_hbm_share.cosmo1e").read(four_chip_run({})) is None
    assert reader("device.idle_share.cosmoe").read(run) == pytest.approx(
        100 * (1 - (400 + 600 + 300 + 200) / 4 / 1000))
