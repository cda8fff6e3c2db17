"""Fig. 13 (repo extension): multi-field IR programs — parity + per-field bytes.

NERO pairs hdiff with vertical advection (vadvc) and StencilFlow treats
weather programs as dataflow graphs over many named fields; this benchmark
measures what the multi-field IR stack (ISSUE 5) delivers for the two new
workloads, ``vadvc`` (velocity + scalar, both radius k) and
``hdiff_coupled`` (hdiff with a radius-0 diffusion-coefficient field):

  * single-device parity: the fused multi-input Pallas kernel (interpret
    mode on CPU) vs the composed reference oracle, k in {1, 2} — hard
    failure past 1e-6, like fig10/fig12;
  * graph-derived per-field accounting: reads per field (summing to the
    program total) and compulsory HBM bytes per simulated step (every
    field in once + output once, / k);
  * a REAL 8-fake-device run (subprocess): sharded parity on a 2 x 4
    rows x cols mesh and measured per-chip collective-permute bytes vs the
    per-field wire model ``program_halo_exchange_bytes_per_shard`` —
    hdiff_coupled at k=1 must move ZERO coefficient bytes, and every ratio
    must be exactly 1.000;
  * RESULTMO (ISSUE 8): the multi-OUTPUT coupled shallow-water system on
    the same 2 x 4 mesh, comparing the MERGED halo exchange (one stacked
    collective covering all evolving fields) against the sequential
    per-field baseline (``merge_exchange=False``): same per-chip bytes
    (both at ratio 1.000 vs the summed wire model), 8 vs 24 permutes, and
    the measured wall-clock for each.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp

from benchmarks.common import COLS, ROWS, emit, time_stats
from repro.ir import (
    hdiff_coupled_program,
    lower_pallas,
    lower_reference,
    repeat,
    shallow_water_program,
    smagorinsky_coeff,
    vadvc_program,
)

KS = (1, 2)

_REAL_CHECK = """
import numpy as np, jax, jax.numpy as jnp
assert len(jax.devices()) == 8, jax.devices()
from repro.dist import program_halo_exchange_bytes_per_shard
from repro.ir import (
    hdiff_coupled_program, lower_reference, lower_sharded, repeat,
    smagorinsky_coeff, vadvc_program,
)
from repro.dist import parse_collective_bytes

depth, rows, cols = {depth}, {rows}, {cols}
R, C = 2, 4
rng = np.random.default_rng(0)
g = lambda: jnp.asarray(rng.standard_normal((depth, rows, cols)).astype(np.float32))
cases = {{
    "vadvc": (vadvc_program(), {{"s": g(), "w": g()}}),
    "hdiff_coupled": (hdiff_coupled_program(), {{
        "u": g(),
        "coeff": jnp.asarray(
            smagorinsky_coeff(rng.standard_normal((depth, rows, cols)))),
    }}),
}}
for name, (prog, arrs) in cases.items():
    for k in (1, 2):
        pk = repeat(prog, k)
        want = np.asarray(lower_reference(pk)(arrs))
        fn = lower_sharded(pk, mesh_shape=(R, C), inner="reference")
        np.testing.assert_allclose(np.asarray(fn(arrs)), want, rtol=1e-6, atol=1e-6)
        coll = parse_collective_bytes(jax.jit(fn).lower(arrs).compile().as_text())
        measured = coll["bytes"].get("collective-permute", 0.0)
        model = program_halo_exchange_bytes_per_shard(
            pk, depth, rows // R, cols // C, row_sharded=True, col_sharded=True)
        print(f"RESULT name={{name}} k={{k}} measured={{measured:.0f}} "
              f"per_chip_model={{model:.0f}} "
              f"permutes={{coll['counts'].get('collective-permute', 0)}} parity=ok")
"""


_REAL_MO_CHECK = """
import numpy as np, jax, jax.numpy as jnp, time
assert len(jax.devices()) == 8, jax.devices()
from repro.dist import program_halo_exchange_bytes_per_shard
from repro.ir import lower_reference, lower_sharded, repeat, shallow_water_program
from repro.dist import parse_collective_bytes

depth, rows, cols = {depth}, {rows}, {cols}
R, C = 2, 4
rng = np.random.default_rng(0)
g = lambda: jnp.asarray(rng.standard_normal((depth, rows, cols)).astype(np.float32))
arrs = {{"u": g(), "v": g(), "h": g()}}
for k in (1, 2):
    pk = repeat(shallow_water_program(), k)
    want = lower_reference(pk)(arrs)
    model = program_halo_exchange_bytes_per_shard(
        pk, depth, rows // R, cols // C, row_sharded=True, col_sharded=True)
    for mode, merged in (("merged", True), ("sequential", False)):
        fn = jax.jit(lower_sharded(pk, mesh_shape=(R, C), inner="reference",
                                   merge_exchange=merged))
        got = fn(arrs)
        for f in want:
            np.testing.assert_allclose(np.asarray(got[f]), np.asarray(want[f]),
                                       rtol=1e-6, atol=1e-6, err_msg=f)
        coll = parse_collective_bytes(fn.lower(arrs).compile().as_text())
        jax.block_until_ready(fn(arrs))
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arrs))
            times.append(time.perf_counter() - t0)
        times.sort()
        print(f"RESULTMO mode={{mode}} k={{k}} "
              f"measured={{coll['bytes'].get('collective-permute', 0.0):.0f}} "
              f"per_chip_model={{model:.0f}} "
              f"permutes={{coll['counts'].get('collective-permute', 0)}} "
              f"median_us={{times[1] * 1e6:.1f}} parity=ok")
"""


def run(fast: bool = False) -> None:
    depth = 2 if fast else 8  # interpret-mode Pallas: keep planes modest
    rng = np.random.default_rng(0)
    g = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((depth, ROWS, COLS)).astype(np.float32)
    )
    cases = {
        "vadvc": (vadvc_program(), {"s": g(), "w": g()}),
        "hdiff_coupled": (hdiff_coupled_program(), {
            "u": g(),
            "coeff": jnp.asarray(
                smagorinsky_coeff(rng.standard_normal((depth, ROWS, COLS)))
            ),
        }),
    }
    for name, (prog, arrs) in cases.items():
        points = arrs[prog.passthrough].size
        for k in KS:
            pk = repeat(prog, k)
            fn = lower_pallas(pk)
            want = np.asarray(lower_reference(pk)(arrs))
            got = np.asarray(fn(arrs))
            err = float(np.max(np.abs(got - want)))
            if err > 1e-6:
                raise AssertionError(
                    f"{name} k={k}: fused multi-input Pallas diverges from "
                    f"composed reference: max|d|={err:.1e}"
                )
            ts = time_stats(fn, arrs, warmup=1, iters=3)
            reads = pk.reads_by_field()
            emit(
                f"fig13/{name}_k{k}",
                ts.median_us / k,
                f"min_us={ts.min_us / k:.1f} "
                f"parity=ok(max|d|={err:.1e}) "
                f"hbm_bytes_per_step={pk.fused_bytes_per_step(points):.0f} "
                f"({len(pk.inputs)} fields in + out, /{k}) "
                f"reads_by_field={'+'.join(f'{f}:{n}' for f, n in reads.items())}"
                f"={sum(reads.values())} field_radii={pk.field_radii()}",
            )

    # Multi-OUTPUT single-device rows: the fused kernel writes all three
    # shallow-water outputs in one pass; parity is per output field.
    sw = shallow_water_program()
    sw_arrs = {f: g() for f in sw.inputs}
    points = sw_arrs[sw.passthrough].size
    for k in KS:
        pk = repeat(sw, k)
        fn = lower_pallas(pk)
        want = lower_reference(pk)(sw_arrs)
        got = fn(sw_arrs)
        err = max(
            float(np.max(np.abs(np.asarray(got[f]) - np.asarray(want[f]))))
            for f in want
        )
        if err > 1e-6:
            raise AssertionError(
                f"shallow_water k={k}: fused multi-output Pallas diverges "
                f"from composed reference: max|d|={err:.1e}"
            )
        ts = time_stats(fn, sw_arrs, warmup=1, iters=3)
        emit(
            f"fig13/shallow_water_k{k}",
            ts.median_us / k,
            f"min_us={ts.min_us / k:.1f} "
            f"parity=ok(max|d|={err:.1e}) "
            f"outputs={'+'.join(pk.outputs)} "
            f"hbm_bytes_per_step={pk.fused_bytes_per_step(points):.0f} "
            f"({len(pk.inputs)} fields in + {len(pk.outputs)} out, /{k}) "
            f"output_radii={pk.output_radii()}",
        )

    # REAL 8-fake-device run: sharded parity + measured per-field wire bytes.
    real_multifield_check(depth, ROWS, COLS)

    # RESULTMO: merged vs sequential exchange for the coupled system.
    real_multioutput_check(depth, ROWS, COLS)


def real_multifield_check(depth: int, rows: int, cols: int) -> None:
    """Runs _REAL_CHECK in a child with 8 fake devices; emits measured
    per-chip collective bytes against the per-field model per program/k."""
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
        emit("fig13/real_8dev", 0.0, f"FAILED: {proc.stderr[-200:]!r}", unit="error")
        raise RuntimeError(f"real 8-device multi-field run failed:\n{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if not line.startswith("RESULT "):
            continue
        fields = dict(kv.split("=") for kv in line.split()[1:])
        measured, model = float(fields["measured"]), float(fields["per_chip_model"])
        emit(
            f"fig13/real_8dev_{fields['name']}_k{fields['k']}",
            measured,
            f"per-chip permute bytes, per-field sum; model={model:.0f} "
            f"ratio={measured / model if model else float('nan'):.6f} "
            f"permutes={fields['permutes']} parity={fields['parity']} "
            f"(2x4 rows x cols mesh; hdiff_coupled k=1 moves zero coeff bytes)",
            unit="bytes",
        )
        if measured != model:
            raise RuntimeError(
                f"multi-field wire bytes diverged from the per-field model: "
                f"{fields['name']} k={fields['k']} measured={measured} model={model}"
            )


def real_multioutput_parse(stdout: str) -> dict[tuple[str, str], dict[str, str]]:
    """RESULTMO lines as ``{(mode, k): fields}`` — split out for testing."""
    rows = {}
    for line in stdout.splitlines():
        if not line.startswith("RESULTMO "):
            continue
        fields = dict(kv.split("=") for kv in line.split()[1:])
        rows[(fields["mode"], fields["k"])] = fields
    return rows


def real_multioutput_check(depth: int, rows: int, cols: int) -> None:
    """Runs _REAL_MO_CHECK in a child with 8 fake devices: the coupled
    shallow-water system, merged vs sequential halo exchange on the 2 x 4
    mesh. Emits per-chip byte rows (both modes must sit at ratio 1.000
    against the summed per-output wire model — the merged exchange changes
    the PERMUTE COUNT, 8 vs 24, never the bytes) and the measured
    wall-clock row for each mode."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    proc = subprocess.run(
        [sys.executable, "-c", _REAL_MO_CHECK.format(depth=depth, rows=rows, cols=cols)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    if proc.returncode != 0:
        emit("fig13/real_8dev_multioutput", 0.0, f"FAILED: {proc.stderr[-200:]!r}",
             unit="error")
        raise RuntimeError(
            f"real 8-device multi-output run failed:\n{proc.stderr[-2000:]}"
        )
    parsed = real_multioutput_parse(proc.stdout)
    for (mode, k), fields in sorted(parsed.items()):
        measured, model = float(fields["measured"]), float(fields["per_chip_model"])
        emit(
            f"fig13/real_8dev_shallow_water_{mode}_k{k}",
            measured,
            f"per-chip permute bytes, merged-vs-sequential exchange; "
            f"model={model:.0f} "
            f"ratio={measured / model if model else float('nan'):.6f} "
            f"permutes={fields['permutes']} parity={fields['parity']} "
            f"(2x4 rows x cols mesh, outputs u+v+h)",
            unit="bytes",
        )
        emit(
            f"fig13/real_8dev_shallow_water_{mode}_k{k}_wall",
            float(fields["median_us"]),
            f"median step wall-clock, {mode} exchange, 8 fake CPU devices "
            f"(permutes={fields['permutes']})",
            unit="model_us",
        )
        if measured != model:
            raise RuntimeError(
                f"multi-output wire bytes diverged from the summed model: "
                f"{mode} k={k} measured={measured} model={model}"
            )
    for k in ("1", "2"):
        merged, seq = parsed[("merged", k)], parsed[("sequential", k)]
        if merged["measured"] != seq["measured"]:
            raise RuntimeError(
                f"merged exchange changed wire bytes at k={k}: "
                f"{merged['measured']} != {seq['measured']}"
            )
        if not (int(merged["permutes"]) < int(seq["permutes"])):
            raise RuntimeError(
                f"merged exchange did not reduce permute count at k={k}: "
                f"{merged['permutes']} vs {seq['permutes']}"
            )
