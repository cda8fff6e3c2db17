"""Subprocess body: ``ForecastServer`` on a 2x2 mesh of fake devices (run
by tests/test_serve_forecast.py with XLA_FLAGS forcing 4 devices).

For a single-output and a multi-output program on the sharded reference
backend, each member's served result must be bit-identical to slice i of
the cache entry's own batched output and carry the same per-member
sharding: the member axis is unsharded, so slicing it inside the batch's
one compiled call keeps the (rows, cols) split of the grid.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.ir import hdiff_program, shallow_water_program  # noqa: E402
from repro.serve import ForecastServer  # noqa: E402

assert len(jax.devices()) >= 4, jax.devices()

n_cases = 0
for prog in (hdiff_program(), shallow_water_program()):
    srv = ForecastServer(backend="sharded-reference", mesh_shape=(2, 2), max_batch=3)
    rng = np.random.default_rng(7)
    fields = [{f: jnp.asarray(rng.standard_normal((2, 16, 16)), jnp.float32)
               for f in prog.inputs} for _ in range(3)]
    rids = [srv.submit(prog, f) for f in fields]
    done = {r.rid: r for r in srv.run_until_idle()}
    (key,) = srv.cache.keys()
    out = srv.cache.lookup(key).fn({f: jnp.stack([m[f] for m in fields]) for f in prog.inputs})
    out = out if isinstance(out, dict) else {"out": out}
    for i, rid in enumerate(rids):
        res = done[rid].result
        res = res if isinstance(res, dict) else {"out": res}
        assert sorted(res) == sorted(out), (sorted(res), sorted(out))
        for f, batched in out.items():
            want = batched[i]
            np.testing.assert_array_equal(np.asarray(res[f]), np.asarray(want))
            assert res[f].sharding.is_equivalent_to(want.sharding, want.ndim), (
                prog.name, f, res[f].sharding, want.sharding)
            assert len(res[f].sharding.device_set) == 4, res[f].sharding
        n_cases += 1
    print(f"{prog.name} mesh=2x2 served members ok")

print(f"ALL_OK {n_cases} members")
