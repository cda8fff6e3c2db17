"""Fixtures of the benchmark's own tests, which run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

Four virtual CPU devices stand in for the 2x2 mesh, so the flag is set
before anything imports JAX. ``tiny_root`` is a copy of the benchmark with
every configuration cut to a few points and every call to a few steps: the
same drivers, traffic mixes and checks at a size a test run holds.
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

TINY = {"cosmo1_hdiff": {"depth": 2, "rows": 24, "cols": 40},
        "sparta_ens21": {"depth": 2, "rows": 16, "cols": 24, "members": 5}}
TINY_TRAFFIC = {"forecast": {"steps_per_call": 4}, "halo_2x2": {"steps_per_call": 4}}


def _edit(path, changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def copy_bench(dest):
    """A copy of the benchmark (``BENCHMARK.json`` and ``bench/``) at ``dest``."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    return str(dest)


# Cells whose files are ready and whose checks are tested here, but which
# wait for their chip runs before they join BENCHMARK.json (PERF.md, open
# questions), with the end-to-end metric each reports.
WAITING_CELLS = [
    ({"name": "cosmo1_hdiff.halo_2x2", "config": "cosmo1_hdiff", "traffic": "halo_2x2",
      "chips": 4, "why": "the forecast loop through lower_sharded on a 2x2 mesh"},
     {"name": "grid_updates_per_s", "unit": "Gpt/s"}),
    ({"name": "cosmo1_hdiff.adjoint", "config": "cosmo1_hdiff", "traffic": "adjoint",
      "chips": 1, "why": "back-to-back gradient steps through the derived adjoint kernels"},
     {"name": "adjoint_steps_per_s", "unit": "steps/s"}),
]


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("tiny"))
    manifest = os.path.join(root, "BENCHMARK.json")
    with open(manifest) as f:
        data = json.load(f)
    for cell, metric in WAITING_CELLS:
        if cell["name"] in {w["name"] for w in data["workloads"]}:
            continue
        data["workloads"].append(cell)
        e2e = {m["name"]: m for m in data["end_to_end"]}
        if metric["name"] in e2e:
            e2e[metric["name"]]["workloads"].append(cell["name"])
        else:
            data["end_to_end"].append({**metric, "better": "higher", "bound": 0.25,
                                       "source": "host_clock", "workloads": [cell["name"]]})
    with open(manifest, "w") as f:
        json.dump(data, f)
    for name, changes in TINY.items():
        _edit(os.path.join(root, "bench", "configs", f"{name}.json"), changes)
    for name, changes in TINY_TRAFFIC.items():
        _edit(os.path.join(root, "bench", "traffic", f"{name}.json"), changes)
    return root
