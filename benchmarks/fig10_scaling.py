"""Fig. 10 analogue: hdiff scaling across compute shards (B-block scaling).

Paper: 1 -> 32 B-blocks scales 32.6x (each block owns a shimDMA channel;
depth-parallel planes -> no contention). TPU mapping: depth-parallel
shard_map over the data axis (zero collectives) and row-decomposition with
halo exchange over the model axis.

On this 1-core CPU container, real multi-device wall time cannot show
speedup, so this benchmark reports:
  * the §3.1-style analytical step time per shard count (what Fig. 10
    measures on hardware), via `plan_partition`,
  * a REAL 8-fake-device correctness + collective-structure run (subprocess),
    recording measured halo bytes vs the model.
"""

from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import COLS, DEPTH, ROWS, emit
from repro.core import plan_partition

# Subprocess body for the REAL run: the main benchmark process must keep
# seeing 1 device (dry-run contract), so the 8-fake-device mesh lives in a
# child. Verifies sharded == single-device on the paper's grid and measures
# the per-chip collective-permute (halo) bytes from compiled HLO against
# the analytical model.
_REAL_CHECK = """
import numpy as np, jax, jax.numpy as jnp
assert len(jax.devices()) == 8, jax.devices()
from repro.core import HALO, hdiff
from repro.dist import halo_exchange_bytes, make_sharded_hdiff, parse_collective_bytes
from repro.launch.mesh import make_mesh

depth, rows, cols, dshards, rshards = {depth}, {rows}, {cols}, 2, 4
mesh = make_mesh((dshards, rshards), ("data", "model"))
fn = make_sharded_hdiff(mesh, depth_axis="data", row_axis="model")

rng = np.random.default_rng(0)
psi = jnp.asarray(rng.standard_normal((depth, rows, cols)).astype(np.float32))
np.testing.assert_allclose(
    np.asarray(fn(psi)), np.asarray(hdiff(psi, 0.025)), rtol=1e-6, atol=1e-6
)

coll = parse_collective_bytes(jax.jit(fn).lower(psi).compile().as_text())
measured = coll["bytes"].get("collective-permute", 0.0)
# parse_collective_bytes reports PER-CHIP bytes (SPMD program, interior
# chip: both halos); halo_exchange_bytes totals the mesh.
per_chip_model = 2 * (depth // dshards) * HALO * cols * 4
print(f"RESULT measured={{measured:.0f}} per_chip_model={{per_chip_model:.0f}} "
      f"mesh_total_model={{halo_exchange_bytes(depth, rows, cols, rshards):.0f}} "
      f"permutes={{coll['counts'].get('collective-permute', 0)}}")

# Temporal blocking: k=2 fused sweeps exchange a depth-2*HALO band ONCE.
from repro.ir import hdiff_program, lower_sharded, repeat
k = 2
fn2 = lower_sharded(repeat(hdiff_program(), k), mesh,
                    depth_axis="data", row_axis="model", inner="reference")
np.testing.assert_allclose(
    np.asarray(fn2(psi)), np.asarray(hdiff(hdiff(psi, 0.025), 0.025)),
    rtol=1e-6, atol=1e-6,
)
coll2 = parse_collective_bytes(jax.jit(fn2).lower(psi).compile().as_text())
measured2 = coll2["bytes"].get("collective-permute", 0.0)
per_chip_model2 = 2 * (depth // dshards) * k * HALO * cols * 4
print(f"RESULT2 measured={{measured2:.0f}} per_chip_model={{per_chip_model2:.0f}} "
      f"mesh_total_model={{halo_exchange_bytes(depth, rows, cols, rshards, steps=k):.0f}} "
      f"permutes={{coll2['counts'].get('collective-permute', 0)}}")

# 2-D rows x cols decomposition (ISSUE 4): row bands + col bands + diagonal
# corners, measured per-chip against the 2-axis model — and overlap=True
# must BIT-match overlap=False at identical wire bytes.
from repro.dist import halo_exchange_bytes_per_shard
from repro.ir import plan_partition
prog = hdiff_program()
plan = plan_partition(prog, depth, rows, cols, 8)
R, C = plan.mesh_shape
fn2d = lower_sharded(prog, mesh_shape=(R, C), inner="reference")
got2d = np.asarray(fn2d(psi))
np.testing.assert_allclose(got2d, np.asarray(hdiff(psi, 0.025)), rtol=1e-6, atol=1e-6)
coll2d = parse_collective_bytes(jax.jit(fn2d).lower(psi).compile().as_text())
measured2d = coll2d["bytes"].get("collective-permute", 0.0)
model2d = halo_exchange_bytes_per_shard(
    depth, rows // R, cols // C, halo=HALO, row_sharded=R > 1, col_sharded=C > 1)
row_m = 2 * depth * HALO * (cols // C) * 4 if R > 1 else 0
col_m = 2 * depth * (rows // R) * HALO * 4 if C > 1 else 0
corner_m = 4 * depth * HALO * HALO * 4 if (R > 1 and C > 1) else 0
assert row_m + col_m + corner_m == model2d, (row_m, col_m, corner_m, model2d)
fo2d = lower_sharded(prog, mesh_shape=(R, C), inner="reference", overlap=True)
ov = np.asarray(fo2d(psi))
bit_match = bool((ov == got2d).all())
collov = parse_collective_bytes(jax.jit(fo2d).lower(psi).compile().as_text())
measured_ov = collov["bytes"].get("collective-permute", 0.0)
assert measured_ov == measured2d, (measured_ov, measured2d)  # overlap moves the same bytes
print(f"RESULT2D mesh={{R}}x{{C}} measured={{measured2d:.0f}} per_chip_model={{model2d:.0f}} "
      f"row_model={{row_m}} col_model={{col_m}} corner_model={{corner_m}} "
      f"mesh_total_model={{halo_exchange_bytes(depth, rows, cols, R, col_shards=C):.0f}} "
      f"permutes={{coll2d['counts'].get('collective-permute', 0)}} "
      f"overlap_bitmatch={{bit_match}} overlap_measured={{measured_ov:.0f}}")

# Multi-field per-field wire sum (ISSUE 5): vadvc exchanges BOTH its fields'
# radius-1 bands on the depth x rows mesh — the per-field model must stay
# measured-exact, like the single-field lines above.
from repro.dist import program_halo_exchange_bytes_per_shard
from repro.ir import vadvc_program
vprog = vadvc_program()
fnmf = lower_sharded(vprog, mesh, depth_axis="data", row_axis="model",
                     inner="reference")
varrs = {{"s": psi, "w": jnp.asarray(rng.standard_normal(psi.shape).astype(np.float32))}}
from repro.ir import lower_reference
np.testing.assert_allclose(
    np.asarray(fnmf(varrs)), np.asarray(lower_reference(vprog)(varrs)),
    rtol=1e-6, atol=1e-6,
)
collmf = parse_collective_bytes(jax.jit(fnmf).lower(varrs).compile().as_text())
measured_mf = collmf["bytes"].get("collective-permute", 0.0)
model_mf = program_halo_exchange_bytes_per_shard(
    vprog, depth // dshards, rows // rshards, cols, row_sharded=True)
print(f"RESULTMF measured={{measured_mf:.0f}} per_chip_model={{model_mf:.0f}} "
      f"permutes={{collmf['counts'].get('collective-permute', 0)}}")
"""


def run(fast: bool = False) -> None:
    shard_counts = [1, 2, 4, 8, 16, 32]
    t1 = None
    for n in shard_counts:
        plan = plan_partition(DEPTH, ROWS, COLS, n)
        if t1 is None:
            t1 = plan.step_s
        speedup = t1 / plan.step_s
        emit(
            f"fig10/shards_{n:02d}",
            plan.step_s * 1e6,
            f"kind={plan.kind} speedup={speedup:.1f}x ici_s={plan.ici_s:.2e}",
            unit="model_us",
        )
    # The paper's headline: 32 blocks -> 32.6x over 1 block (linear).
    plan32 = plan_partition(DEPTH, ROWS, COLS, 32)
    emit("fig10/speedup_at_32", t1 / plan32.step_s,
         f"paper reports 32.6x at 32 B-blocks; depth-parallel model gives "
         f"{t1/plan32.step_s:.1f}x (linear, no collectives)", unit="x")

    # Halo traffic model when forced to row-decompose (beyond 64 shards the
    # paper's plane-parallel strategy runs out of planes; ours does too).
    for n in [64, 128, 256]:
        plan = plan_partition(DEPTH, ROWS, COLS, n)
        emit(
            f"fig10/shards_{n:03d}",
            plan.step_s * 1e6,
            f"kind={plan.kind} rows/shard={ROWS//plan.row_shards} "
            f"ici_s={plan.ici_s:.2e} (halo exchange appears)",
            unit="model_us",
        )

    # 2-D rows x cols factorization: wire bytes per exchange round for every
    # factorization of 8 devices, and the planner's pick (the balanced split
    # minimizes boundary surface — the paper's workload-balance point).
    from repro.dist import halo_exchange_bytes
    from repro.ir import hdiff_program, plan_partition as plan_2d

    prog = hdiff_program()
    for r_sh, c_sh in [(8, 1), (4, 2), (2, 4), (1, 8)]:
        wire = halo_exchange_bytes(
            DEPTH, ROWS, COLS, r_sh, halo=prog.radius, col_shards=c_sh
        )
        emit(
            f"fig10/wire_2d_{r_sh}x{c_sh}",
            wire,
            "mesh-total halo bytes/round, 2-axis model (bands + corners)",
            unit="bytes",
        )
    pick = plan_2d(prog, DEPTH, ROWS, COLS, 8)
    emit(
        "fig10/wire_2d_planned",
        pick.wire_bytes,
        f"plan_partition pick {pick.row_shards}x{pick.col_shards} "
        f"(<= 1-D row baseline "
        f"{halo_exchange_bytes(DEPTH, ROWS, COLS, 8, halo=prog.radius)})",
        unit="bytes",
    )

    # REAL 8-fake-device run: correctness + measured halo bytes vs model.
    depth = 8 if fast else DEPTH
    real_halo_check(depth, ROWS, COLS)


def real_halo_check(depth: int, rows: int, cols: int) -> None:
    """Runs _REAL_CHECK in a child with 8 fake devices and emits the
    measured collective-permute bytes against the analytical model."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    proc = subprocess.run(
        [sys.executable, "-c", _REAL_CHECK.format(depth=depth, rows=rows, cols=cols)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if proc.returncode != 0:
        emit("fig10/real_8dev", 0.0, f"FAILED: {proc.stderr[-200:]!r}", unit="error")
        raise RuntimeError(f"real 8-device halo run failed:\n{proc.stderr[-2000:]}")
    line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT "))
    fields = dict(kv.split("=") for kv in line.split()[1:])
    measured, model = float(fields["measured"]), float(fields["per_chip_model"])
    emit(
        "fig10/real_8dev_halo_bytes",
        measured,
        f"per-chip permute bytes; model={model:.0f} "
        f"ratio={measured / model if model else float('nan'):.6f} "
        f"mesh_total_model={fields['mesh_total_model']} "
        f"permutes={fields['permutes']} (2x4 mesh, depth x row decomposition, "
        f"sharded==single-device verified)",
        unit="bytes",
    )
    line2 = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT2 "))
    fields2 = dict(kv.split("=") for kv in line2.split()[1:])
    measured2, model2 = float(fields2["measured"]), float(fields2["per_chip_model"])
    emit(
        "fig10/real_8dev_halo_bytes_k2",
        measured2,
        f"per-chip permute bytes for ONE exchange serving k=2 fused sweeps; "
        f"model={model2:.0f} ratio={measured2 / model2 if model2 else float('nan'):.6f} "
        f"mesh_total_model={fields2['mesh_total_model']} "
        f"permutes={fields2['permutes']} (exchange ROUNDS per simulated step "
        f"halve; repeat(hdiff,2)==hdiff∘hdiff verified)",
        unit="bytes",
    )
    line3 = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT2D "))
    fields3 = dict(kv.split("=") for kv in line3.split()[1:])
    measured3, model3 = float(fields3["measured"]), float(fields3["per_chip_model"])
    emit(
        "fig10/real_8dev_2d_halo_bytes",
        measured3,
        f"per-chip permute bytes on the planner-chosen {fields3['mesh']} "
        f"rows x cols mesh; model={model3:.0f} "
        f"ratio={measured3 / model3 if model3 else float('nan'):.6f} "
        f"(row_bands={fields3['row_model']} col_bands={fields3['col_model']} "
        f"corners={fields3['corner_model']}) "
        f"mesh_total_model={fields3['mesh_total_model']} "
        f"permutes={fields3['permutes']} (2-D decomposition verified vs "
        f"single-device)",
        unit="bytes",
    )
    emit(
        "fig10/real_8dev_2d_overlap",
        1.0 if fields3["overlap_bitmatch"] == "True" else 0.0,
        f"overlap=True bit-matches overlap=False on the {fields3['mesh']} mesh "
        f"(interior compute issued concurrently with the edge exchange); "
        f"overlap wire bytes {fields3['overlap_measured']} == "
        f"{measured3:.0f} non-overlap",
        unit="bool",
    )
    if fields3["overlap_bitmatch"] != "True":
        raise RuntimeError("overlap=True did not bit-match overlap=False")
    line4 = next(l for l in proc.stdout.splitlines() if l.startswith("RESULTMF "))
    fields4 = dict(kv.split("=") for kv in line4.split()[1:])
    measured4, model4 = float(fields4["measured"]), float(fields4["per_chip_model"])
    emit(
        "fig10/real_8dev_multifield_halo_bytes",
        measured4,
        f"per-chip permute bytes for vadvc (BOTH fields' radius-1 bands, "
        f"per-field sum model); model={model4:.0f} "
        f"ratio={measured4 / model4 if model4 else float('nan'):.6f} "
        f"permutes={fields4['permutes']} (depth x rows mesh, parity verified)",
        unit="bytes",
    )
