"""A new cell, configuration, traffic mix and metric are found by file name:
a throwaway fixture adds only files and manifest entries, edits no file of
the benchmark, and runs."""

import hashlib
import json
import os

from bench import harness
from bench.tests.conftest import copy_bench


def digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, files in sorted(os.walk(os.path.join(root, "bench"))):
        dirnames.sort()
        for name in sorted(files):
            with open(os.path.join(dirpath, name), "rb") as f:
                h.update(name.encode() + f.read())
    return h.hexdigest()


def test_a_fixture_cell_is_found_by_name(tmp_path):
    root = copy_bench(tmp_path)
    before = digest(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "fixture_grid.json"), "w") as f:
        json.dump({"name": "fixture_grid", "program": "hdiff", "coeff": 0.02, "limit": True,
                   "depth": 1, "rows": 16, "cols": 24, "reduced": []}, f)
    with open(os.path.join(bench, "traffic", "fixture_mix.json"), "w") as f:
        json.dump({"driver": "forecast", "steps_per_call": 2, "mesh": None,
                   "trace_seconds": 1, "limits": {"forecast_rel_err": 1e-4}}, f)
    with open(os.path.join(bench, "metrics", "fixture.calls_per_s.py"), "w") as f:
        f.write('def read(run):\n'
                '    return run.window["counts"]["calls"] / run.window["window_s"]\n')
    manifest_path = os.path.join(root, "BENCHMARK.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    real_cells = {w["name"] for w in manifest["workloads"]}
    manifest["workloads"].append({"name": "fixture_grid.fixture_mix", "config": "fixture_grid",
                                  "traffic": "fixture_mix", "chips": 1, "why": "test"})
    manifest["end_to_end"].append({"name": "fixture.calls_per_s", "unit": "calls/s",
                                   "better": "higher", "bound": 0.1, "source": "host_clock",
                                   "workloads": ["fixture_grid.fixture_mix"]})
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    result, _ = harness.run_cell("fixture_grid.fixture_mix", 3, 0.2, False, root=root,
                                 require_chip=False)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"fixture.calls_per_s", "setup_s"}
    assert result["metrics"]["fixture.calls_per_s"]["value"] > 0
    # Nothing the benchmark already had was edited; the real manifest has no fixture.
    new = {"fixture_grid.json", "fixture_mix.json", "fixture.calls_per_s.py"}
    assert digest(root) != before
    for name in new:
        os.remove(next(os.path.join(d, name) for d, _, fs in os.walk(bench) if name in fs))
    assert digest(root) == before
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        assert "fixture_grid.fixture_mix" not in {w["name"] for w in json.load(f)["workloads"]}
    assert "fixture_grid.fixture_mix" not in real_cells


def test_no_chip_is_refused():
    import pytest

    with pytest.raises(harness.NoChip):
        harness.device_info(1)
