"""The COSMO-E cell (``cosmoe_ens21.served_fields``) driven through whole
runs on the CPU, on its own tiny copy of the benchmark (rows and lanes that
pad, five fields, five members): sound, it reads correct; with the control
(the plain reference in bfloat16) or a fault in one member in place of the
timed path, it reads not correct."""

import json
import os

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.conftest import copy_bench

CELL = "cosmoe_ens21.served_fields"
SEED = 2**31 + 1013
SECONDS = 0.3
TINY = {"depth": 2, "rows": 39, "cols": 58, "members": 5}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("cosmoe"))
    path = os.path.join(root, "bench", "configs", "cosmoe_ens21.json")
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


def test_sound_run_is_correct(root):
    result = run(root)
    assert result["correct"], result["checks"]
    assert result["checks"]["member_rel_err"]["value"] == 0.0
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"served_members_per_s", "served_p95_ms", "setup_s"}


def test_control_is_not_correct(root):
    cell = harness.find_cell(CELL, root)
    mod = harness.load_module(f"{cell.bench}/drivers/{cell.traffic['driver']}.py",
                              "bench_driver_cosmoe")
    result = run(root, mod.control(cell.config, cell.traffic))
    assert not result["correct"], result["checks"]


def _one_member_altered(fn):
    """One point of one field of the batch's last member moved."""
    def step(fields):
        out = dict(fn(fields))
        out["t"] = out["t"].at[-1, 0, 3, 5].add(1e-2)
        return out
    return step


def _one_member_stale(fn):
    """The batch's last member keeps its old pressure field."""
    def step(fields):
        out = dict(fn(fields))
        out["pp"] = jnp.concatenate([out["pp"][:-1], fields["pp"][-1:]])
        return out
    return step


@pytest.mark.parametrize("fault", [_one_member_altered, _one_member_stale],
                         ids=["answer_altered", "state_unchanged"])
def test_one_member_fault_is_not_correct(root, fault):
    result = run(root, fault)
    assert not result["correct"], result["checks"]
