"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived,unit`` CSV rows. ``--fast`` shrinks the grid
depth for quick CI-style runs; full runs use the paper's 256x256x64 domain.

Set ``REPRO_TRACE_DIR=/some/dir`` to capture a ``jax.profiler`` trace per
benchmark (one subdirectory each, viewable in Perfetto / TensorBoard).
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="reduced depth for quick runs")
    ap.add_argument("--only", default="", help="comma-separated benchmark names")
    args = ap.parse_args()

    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        analytical_vs_compiled,
        fig1_roofline,
        fig9_designs,
        fig10_scaling,
        fig11_elementary,
        fig12_temporal,
        fig13_multifield,
        table2_comparison,
    )

    benches = {
        "fig1": fig1_roofline.run,
        "fig9": fig9_designs.run,
        "fig10": fig10_scaling.run,
        "fig11": fig11_elementary.run,
        "fig12": fig12_temporal.run,
        "fig13": fig13_multifield.run,
        "table2": table2_comparison.run,
        "analytic": analytical_vs_compiled.run,
    }
    only = {s for s in args.only.split(",") if s}

    from repro.obs import maybe_trace

    print("name,value,derived,unit")
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            with maybe_trace(name):
                fn(fast=args.fast)
        except Exception as e:
            failed.append(name)
            print(f"{name}/ERROR,0,{e!r}", file=sys.stderr)
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
