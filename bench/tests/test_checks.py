"""Each cell's check, driven through a whole run on the CPU at a tiny size:
sound, it reads correct; with the control (the plain reference in bfloat16)
or a fault in place of the timed path, it reads not correct."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from bench import harness

CELLS = ("cosmo1_hdiff.forecast", "cosmo1_hdiff.halo_2x2", "sparta_ens21.served",
         "cosmo1_hdiff.adjoint")
SEED = 2**31 + 977
SECONDS = 0.3


def run(root, workload, timed_path=None):
    result, checks = harness.run_cell(workload, SEED, SECONDS, False, root=root,
                                      require_chip=False, timed_path=timed_path)
    return result


def driver(root, workload):
    cell = harness.find_cell(workload, root)
    mod = harness.load_module(f"{cell.bench}/drivers/{cell.traffic['driver']}.py",
                              "bench_driver_test")
    return cell, mod


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    result = run(tiny_root, workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_root, workload):
    cell, mod = driver(tiny_root, workload)
    result = run(tiny_root, workload, mod.control(cell.config, cell.traffic))
    assert not result["correct"], result["checks"]


def _altered(out):
    return out.at[..., 3, 5].add(1e-2)


def _no_exchange(step):
    """lower_sharded's shards, each stepped alone: no halo or corner
    exchange, so every shard edge is treated as the domain's."""
    from repro.ir import hdiff_program, lower_pallas
    from repro.launch.mesh import make_mesh

    inner = lower_pallas(hdiff_program())
    spec = P(None, "rows", "cols")
    return jax.shard_map(inner, mesh=make_mesh((2, 2), ("rows", "cols")), in_specs=spec,
                         out_specs=spec, check_vma=False)


def _half_batch(fn):
    """The second half of each batch left out; it gets the mean of the rest."""
    def step(fields):
        out = fn(fields)
        keep = out.shape[0] - out.shape[0] // 2
        mean = jnp.mean(out[:keep], axis=0, keepdims=True)
        return jnp.concatenate([out[:keep], jnp.broadcast_to(mean, out[keep:].shape)])
    return step


FAULTS = {
    "cosmo1_hdiff.forecast": {
        "state_unchanged": lambda step: lambda x: x,
        "answer_altered": lambda step: lambda x: _altered(step(x)),
    },
    "cosmo1_hdiff.halo_2x2": {
        "state_unchanged": lambda step: lambda x: x,
        "answer_altered": lambda step: lambda x: _altered(step(x)),
        "exchange_left_out": _no_exchange,
    },
    "sparta_ens21.served": {
        "state_unchanged": lambda fn: lambda fields: next(iter(fields.values())),
        "answer_altered": lambda fn: lambda fields: _altered(fn(fields)),
        "half_batch_left_out": _half_batch,
    },
    "cosmo1_hdiff.adjoint": {
        "state_unchanged": lambda step: lambda x, y: (x, step(x, y)[1]),
        "answer_altered": lambda step: lambda x, y: (lambda x1, g: (x1, _altered(g)))(*step(x, y)),
    },
}


@pytest.mark.parametrize("workload,fault", [(w, f) for w in CELLS for f in FAULTS[w]])
def test_fault_is_not_correct(tiny_root, workload, fault):
    result = run(tiny_root, workload, FAULTS[workload][fault])
    assert not result["correct"], (fault, result["checks"])
