"""Subprocess body: the served mesh path of the COSMO-1E cell at a tiny,
ragged size (run by tests/test_serve_forecast.py with XLA_FLAGS forcing 4
devices).

``hdiff_fields_program`` (u, v, w, t, pp) through
``ForecastServer(backend="sharded-pallas", mesh_shape=(2, 2))`` at its
default ``max_batch`` of 8: 11 members on a (2, 38, 58) grid, so batches of
8 and 3 of 19 x 29 shards, which tile in neither rows nor lanes. Each
member's result must equal ``lower_reference`` on that member at the
conformance tolerance and keep the (rows, cols) split over all four
devices. The server must count each batch's exchange on the host:
``stats["halo_bytes"]`` and ``serve.forecast.halo_bytes`` are the members
times one call's per-chip model bytes, beside one ``halo.exchange_rounds``
and one ``halo.exchange`` event per batch; a one-chip server counts none.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from conformance import TOL  # noqa: E402
from repro.dist.halo import call_exchange_bytes  # noqa: E402
from repro.ir import hdiff_fields_program, lower_reference  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.obs import events, metrics  # noqa: E402
from repro.serve import ForecastServer  # noqa: E402

assert len(jax.devices()) >= 4, jax.devices()

GRID = (2, 38, 58)
MEMBERS = 11
prog = hdiff_fields_program(("u", "v", "w", "t", "pp"), (0.025,) * 5)
sharding = NamedSharding(make_mesh((2, 2), ("rows", "cols")), P(None, "rows", "cols"))
rng = np.random.default_rng(19)
members = [{f: jax.device_put(jnp.asarray(rng.standard_normal(GRID), jnp.float32), sharding)
            for f in prog.inputs} for _ in range(MEMBERS)]
per_call = call_exchange_bytes(prog, GRID, (1, 2, 2))
# Row bands 2 x (2, 2, 29), col bands 2 x (2, 19, 2), corners 4 x (2, 2, 2),
# f32, for each of the five fields.
assert per_call == {f: 4 * (2 * 2 * 2 * 29 + 2 * 2 * 19 * 2 + 4 * 2 * 2 * 2)
                    for f in prog.inputs}, per_call

with metrics.using() as reg, events.using() as rec:
    srv = ForecastServer(backend="sharded-pallas", mesh_shape=(2, 2))
    rids = [srv.submit(prog, m) for m in members]
    done = {r.rid: r for r in srv.run_until_idle()}
batches = sorted(k.batch for k in srv.cache.keys())
assert batches == [3, 8], batches
ref = lower_reference(prog)
for i, rid in enumerate(rids):
    res = done[rid].result
    want = ref({f: np.asarray(a) for f, a in members[i].items()})
    assert sorted(res) == sorted(want), (sorted(res), sorted(want))
    for f in want:
        np.testing.assert_allclose(np.asarray(res[f]), np.asarray(want[f]),
                                   rtol=TOL, atol=TOL, err_msg=f"member {i} field {f}")
        assert res[f].sharding.is_equivalent_to(sharding, 3), (i, f, res[f].sharding)
        assert len(res[f].sharding.device_set) == 4, res[f].sharding
print(f"{MEMBERS} members x {len(prog.inputs)} fields match lower_reference, 2x2 kept")

halo_bytes = MEMBERS * sum(per_call.values())
assert srv.stats["halo_bytes"] == halo_bytes, (srv.stats["halo_bytes"], halo_bytes)
assert reg.counters["serve.forecast.halo_bytes"] == halo_bytes, reg.counters
assert reg.counters["halo.exchange_rounds"] == 2, reg.counters
for f, nbytes in per_call.items():
    assert reg.counters[f"halo.model_bytes.{f}"] == MEMBERS * nbytes, reg.counters
exchanges = rec.events("halo.exchange")
assert [(e.data["batch"], e.data["mesh"], e.data["bytes"]) for e in exchanges] == [
    (0, [2, 2], 8 * sum(per_call.values())), (1, [2, 2], 3 * sum(per_call.values()))
], [e.data for e in exchanges]
print(f"halo_bytes {halo_bytes} = {MEMBERS} members x {sum(per_call.values())}")

with metrics.using() as reg, events.using() as rec:
    one = ForecastServer(backend="pallas")
    for m in members[:3]:
        one.submit(prog, {f: np.asarray(a) for f, a in m.items()})
    one.run_until_idle()
assert one.stats["halo_bytes"] == 0, one.stats
assert not any(k.startswith(("halo.", "serve.forecast.halo")) for k in reg.counters), reg.counters
assert not rec.events("halo.exchange")
print("one-chip server counts no exchange")
print("ALL_OK")
