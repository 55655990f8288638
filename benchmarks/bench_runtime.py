"""End-to-end runtime benchmark: both mining applications through
``GridRuntime`` + the grid workflow engine, with real (Pallas) kernels
feeding the simulated clock.

Emits the usual CSV rows AND writes a machine-readable
``BENCH_runtime.json`` so CI can track the perf trajectory per-PR:

    {"meta": {...},
     "vclustering": {"wall_s", "compute_s", "overhead_pct", "rounds",
                     "bytes", "sync_mode", "n_global"},
     "gfm":         {"wall_s", "compute_s", "overhead_pct", "rounds",
                     "bytes", "n_frequent"},
     "fdm":         {... same keys as gfm ...}}

    PYTHONPATH=src python -m benchmarks.bench_runtime [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import platform

import jax

from benchmarks.common import row


def _report_block(run, rounds: int, comm_bytes: int, extra: dict) -> dict:
    rep = run.report
    return {
        "wall_s": rep.wall_s,
        "compute_s": rep.compute_s,
        "critical_compute_s": rep.critical_compute_s,
        "critical_transfer_s": rep.critical_transfer_s,
        "overhead_pct": rep.overhead_pct(),
        "prep_s": rep.prep_s,
        "submit_s": rep.submit_s,
        "transfer_s": rep.transfer_s,
        "rounds": rounds,
        "bytes": comm_bytes,
        "n_jobs": len(rep.job_times),
        "sync_mode": run.sync_mode,
        "schedule": run.schedule,
        "estimated_s": run.estimated_s,
        "estimated_staged_s": run.estimated_staged_s,
        "est_overhead_pct": run.est_overhead_pct(),
        **extra,
    }


def run(
    smoke: bool = False,
    out: str = "BENCH_runtime.json",
    use_kernel: bool | None = None,
    schedule: str = "staged",
    exec_backend: str = "inline",
) -> dict:
    from repro.core.apriori import TransactionDB
    from repro.core.vclustering import VClusterConfig
    from repro.data.synthetic import (
        gaussian_mixture,
        ibm_transactions,
        split_sites,
        split_transactions,
    )
    from repro.runtime import GridRuntime

    if use_kernel is None:
        # Pallas kernels compile natively on TPU; on CPU they run in
        # interpret mode, tractable only at smoke sizes
        use_kernel = smoke or jax.default_backend() == "tpu"

    n_sites = 4
    if smoke:
        n_pts, dim, k_local, iters = 1200, 2, 6, 10
        n_tx, n_items, k_items, minsup = 800, 24, 3, 0.1
    else:
        n_pts, dim, k_local, iters = 20_000, 8, 12, 25
        n_tx, n_items, k_items, minsup = 8000, 60, 4, 0.05

    pts, _ = gaussian_mixture(0, n_pts, dim, 4, spread=12.0, sigma=0.6)
    xs = split_sites(pts, n_sites, seed=1)
    dense = ibm_transactions(seed=2, n_tx=n_tx, n_items=n_items, avg_tx_len=8, n_patterns=10)
    sites = [TransactionDB.from_dense(s) for s in split_transactions(dense, n_sites, seed=0)]

    backend = "kernel" if use_kernel else "jnp"
    rt = GridRuntime.for_sites(
        n_sites, use_kernel=use_kernel, count_backend=backend, schedule=schedule,
        backend=exec_backend,
    )
    cfg = VClusterConfig(k_local=k_local, kmeans_iters=iters, use_kernel=use_kernel)

    vrun = rt.run_vclustering(jax.random.PRNGKey(0), xs, cfg)
    vres = vrun.result
    row(
        "runtime_vclustering_wall",
        vrun.report.wall_s,
        f"overhead={vrun.report.overhead_pct():.1f}%;sync={vrun.sync_mode}",
    )
    row("runtime_vclustering_compute", vrun.report.compute_s, f"n_global={int(vres.merged.n_global)}")

    grun = rt.run_gfm(sites, k_items, minsup)
    gres = grun.result
    row("runtime_gfm_wall", grun.report.wall_s, f"overhead={grun.report.overhead_pct():.1f}%")
    row(
        "runtime_gfm_compute",
        grun.report.compute_s,
        f"rounds={gres.comm.rounds};frequent={len(gres.frequent)}",
    )

    frun = rt.run_fdm(sites, k_items, minsup)
    fres = frun.result
    row(
        "runtime_fdm_compute",
        frun.report.compute_s,
        f"rounds={fres.comm.rounds};frequent={len(fres.frequent)}",
    )

    payload = {
        "meta": {
            "smoke": smoke,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "jax_backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "n_sites": n_sites,
            "schedule": schedule,
            "exec_backend": exec_backend,
            "clustering_shape": [n_pts, dim, k_local],
            "itemsets_shape": [n_tx, n_items, k_items, minsup],
        },
        "vclustering": _report_block(
            vrun,
            rounds=1,  # the single stats all_gather
            comm_bytes=int(vres.comm_bytes),
            extra={"n_global": int(vres.merged.n_global)},
        ),
        "gfm": _report_block(
            grun,
            rounds=gres.comm.rounds,
            comm_bytes=gres.comm.bytes_sent,
            extra={"n_frequent": len(gres.frequent)},
        ),
        "fdm": _report_block(
            frun,
            rounds=fres.comm.rounds,
            comm_bytes=fres.comm.bytes_sent,
            extra={"n_frequent": len(fres.frequent)},
        ),
    }
    if out:
        out_path = pathlib.Path(out)
        if out_path.parent != pathlib.Path("."):
            out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(payload, indent=2))
        print(f"# wrote {out}", flush=True)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny shapes for CI")
    ap.add_argument("--out", default="BENCH_runtime.json")
    ap.add_argument(
        "--kernel",
        choices=["auto", "on", "off"],
        default="auto",
        help="Pallas kernels: auto = smoke/TPU only",
    )
    ap.add_argument(
        "--schedule",
        choices=["staged", "async"],
        default="staged",
        help="engine scheduler: stage-barrier or event-driven",
    )
    ap.add_argument(
        "--backend",
        choices=["inline", "batched", "multihost"],
        default="inline",
        help="execution backend: per-job host loop, fused vmapped fan-outs, "
        "or the jax.distributed site-ownership backend (single-process "
        "fallback unless launched under a coordinator; under one, each "
        "process executes only its owned sites and ships results)",
    )
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run(
        smoke=args.smoke,
        out=args.out,
        use_kernel=None if args.kernel == "auto" else args.kernel == "on",
        schedule=args.schedule,
        exec_backend=args.backend,
    )


if __name__ == "__main__":
    main()
