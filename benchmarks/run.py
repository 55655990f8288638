"""Benchmark harness entry — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run --only gfm
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter")
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        bench_clustering,
        bench_gfm_vs_fdm,
        bench_kernels,
        bench_overheads,
        bench_runtime,
        bench_scaling,
    )

    benches = [
        ("gfm_vs_fdm (paper §5.2.1, Table 3 rows 2-3)", bench_gfm_vs_fdm.run),
        ("clustering (paper §5.2.1, Table 3 row 1)", bench_clustering.run),
        ("overheads (paper Table 3 / §5.2.2)", bench_overheads.run),
        ("scaling (grid dimension)", bench_scaling.run),
        ("kernels (hot-spot microbench)", bench_kernels.run),
        ("runtime (end-to-end apps through GridRuntime)", bench_runtime.run),
    ]

    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
