"""Service-level throughput/latency bench: a bursty multi-tenant arrival
trace through the continuous mining service (``repro.launch.serve``).

Where the sweep benches measure ONE application's DAG, this measures the
serving layer itself: request throughput, tenant-visible latency
percentiles (admission to completion, queue wait included) overall and
PER TENANT, the versioned cache's hit rate across bursts and data
appends, how many identical concurrent requests coalesced into shared
executions, how many execution groups the cross-request batcher fused
into shared device dispatches, and the round-robin fairness bound over
the pick log.  The trace is the same seeded burst generator the service
CLI drives (shared query per burst -> coalescing; a same-app sibling
query per burst -> cross-request fusion; small param pool -> repeats
within a dataset version -> cache hits; periodic appends -> version
bumps -> honest misses).

``--slo BENCH_service_slo.json`` turns the bench into a gate: the report
is checked against committed latency bands (p50/p95 overall and per
tenant), the fairness bound, the fusion invariant
(``device_dispatches < executions``), and — because the gate first
replays the SAME trace with fusion disabled — the wall-time invariant
that fused execution is never slower than serial beyond a tolerance.
The serial pass runs FIRST, so jit warm-up is charged to it, not to the
fused pass being gated.

    PYTHONPATH=src python -m benchmarks.bench_service --smoke --out BENCH_service.json
    PYTHONPATH=src python -m benchmarks.bench_service --smoke --slo BENCH_service_slo.json
"""

from __future__ import annotations

import argparse
import json
import time
from types import SimpleNamespace

import numpy as np

from repro.launch.serve import _build_service, _trace_bursts, fairness_violations
from repro.workflow.requests import QueueFullError


def _latency_ms(values) -> dict:
    arr = np.array(values) if len(values) else np.zeros(1)
    return {
        "p50": float(np.percentile(arr, 50) * 1e3),
        "p90": float(np.percentile(arr, 90) * 1e3),
        "p95": float(np.percentile(arr, 95) * 1e3),
        "max": float(arr.max() * 1e3),
    }


def run(
    backend: str = "batched",
    requests: int = 50,
    tenants: int = 3,
    burst: int = 4,
    n_sites: int = 4,
    n_items: int = 12,
    append_every: int = 2,
    max_per_step: int = 8,
    seed: int = 0,
    fuse: bool = True,
    out: str | None = None,
) -> dict:
    args = SimpleNamespace(
        backend=backend, requests=requests, tenants=tenants, burst=burst,
        n_sites=n_sites, n_items=n_items, seed=seed, max_depth=256,
        no_fuse=not fuse,
    )
    rng = np.random.default_rng(seed)
    svc = _build_service(args)
    tenant_names = [f"tenant{i}" for i in range(tenants)]
    bursts = _trace_bursts(args, rng)

    from repro.data.synthetic import gaussian_mixture, ibm_transactions

    rejected = 0
    t0 = time.perf_counter()
    for b, burst_reqs in enumerate(bursts):
        for tenant, app, dataset, params in burst_reqs:
            try:
                svc.submit(tenant, app, dataset, params)
            except QueueFullError:
                rejected += 1
        svc.drain(max_requests=max_per_step)
        if append_every and (b + 1) % append_every == 0:
            svc.append_transactions("tx", ibm_transactions(seed + b + 1, 60, n_items))
            pts, _ = gaussian_mixture(seed + b + 1, 60, 2, 3)
            svc.append_points("pts", pts)
    wall = time.perf_counter() - t0

    led = svc.ledger()
    done = [r for r in led["requests"] if r["status"] == "done"]
    waits = np.array([r["queue_wait_s"] for r in done]) if done else np.zeros(1)
    fairness_ok = not fairness_violations(
        svc.pick_log, tenant_names, len(tenant_names) * min(
            sum(1 for r in led["requests"] if r["tenant"] == t) for t in tenant_names))

    report = {
        "backend": led["backend"],
        "fuse_requests": bool(fuse),
        "n_sites": n_sites,
        "tenants": tenants,
        "requests": len(led["requests"]),
        "done": len(done),
        "failed": sum(1 for r in led["requests"] if r["status"] == "failed"),
        "rejected": led["rejected"] + rejected,
        "wall_s": wall,
        "throughput_rps": len(done) / max(wall, 1e-9),
        "latency_ms": _latency_ms([r["service_s"] for r in done]),
        "per_tenant_latency_ms": {
            t: _latency_ms([r["service_s"] for r in done if r["tenant"] == t])
            for t in tenant_names
        },
        "queue_wait_ms_mean": float(waits.mean() * 1e3),
        "cache": led["cache"],
        "executions": led["executions"],
        "coalesced": led["coalesced"],
        "exec_groups": led["exec_groups"],
        "fused_requests": led["fused_requests"],
        "device_dispatches": led["device_dispatches"],
        "fairness_ok": bool(fairness_ok),
        "per_tenant": led["per_tenant"],
    }

    print(f"# mining service, {tenants} tenants x bursty trace, "
          f"backend={report['backend']}, fuse={'on' if fuse else 'off'}")
    print("requests,done,throughput_rps,p50_ms,p95_ms,hit_rate,coalesced,dispatches,fair")
    print(
        f"{report['requests']},{report['done']},{report['throughput_rps']:.2f},"
        f"{report['latency_ms']['p50']:.0f},{report['latency_ms']['p95']:.0f},"
        f"{report['cache']['hit_rate']:.2f},{report['coalesced']},"
        f"{report['device_dispatches']}/{report['executions']},"
        f"{'yes' if fairness_ok else 'NO'}"
    )
    if out:
        with open(out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True, default=float)
        print(f"# wrote {out}")
    return report


def check_slo(report: dict, slo: dict, serial_report: dict | None = None) -> list[str]:
    """SLO bands for one bench report; returns the violations (empty =
    pass).  Band keys (all optional): ``p50_ms_max`` / ``p95_ms_max``
    (overall), ``per_tenant_p95_ms_max`` (every tenant), ``min_done``,
    ``require_fairness``, ``require_fusion`` (device_dispatches <
    executions), and — when a fusion-disabled replay of the same trace
    is supplied — ``fused_vs_serial_tol``: fused wall time must be
    within ``serial * (1 + tol)``."""
    problems: list[str] = []
    lat = report["latency_ms"]
    if "p50_ms_max" in slo and lat["p50"] > slo["p50_ms_max"]:
        problems.append(f"p50 {lat['p50']:.0f}ms > band {slo['p50_ms_max']}ms")
    if "p95_ms_max" in slo and lat["p95"] > slo["p95_ms_max"]:
        problems.append(f"p95 {lat['p95']:.0f}ms > band {slo['p95_ms_max']}ms")
    cap = slo.get("per_tenant_p95_ms_max")
    if cap is not None:
        for t, pl in report["per_tenant_latency_ms"].items():
            if pl["p95"] > cap:
                problems.append(f"tenant {t} p95 {pl['p95']:.0f}ms > band {cap}ms")
    if "min_done" in slo and report["done"] < slo["min_done"]:
        problems.append(f"done {report['done']} < band {slo['min_done']}")
    if slo.get("require_fairness", True) and not report["fairness_ok"]:
        problems.append("fairness bound violated")
    if slo.get("require_fusion", False) and (
        report["device_dispatches"] >= report["executions"]
    ):
        problems.append(
            f"no cross-request fusion: device_dispatches "
            f"{report['device_dispatches']} >= executions {report['executions']}"
        )
    if serial_report is not None:
        tol = float(slo.get("fused_vs_serial_tol", 0.25))
        bound = serial_report["wall_s"] * (1.0 + tol)
        if report["wall_s"] > bound:
            problems.append(
                f"fused wall {report['wall_s']:.2f}s > serial "
                f"{serial_report['wall_s']:.2f}s * (1 + {tol}) = {bound:.2f}s"
            )
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="batched", choices=("inline", "batched", "multihost"))
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--n-sites", type=int, default=4)
    ap.add_argument("--n-items", type=int, default=12)
    ap.add_argument("--append-every", type=int, default=2)
    ap.add_argument("--max-per-step", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable cross-request batched execution")
    ap.add_argument("--smoke", action="store_true",
                    help="small trace for CI (fewer requests, tiny data)")
    ap.add_argument("--slo", default=None, metavar="BANDS_JSON",
                    help="gate the report against committed SLO bands; also "
                         "replays the trace fusion-disabled (FIRST, so jit "
                         "warm-up is charged to the serial pass) and gates "
                         "fused wall time against it")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    kw = dict(
        backend=args.backend, requests=args.requests, tenants=args.tenants,
        burst=args.burst, n_sites=args.n_sites, n_items=args.n_items,
        append_every=args.append_every, max_per_step=args.max_per_step,
        seed=args.seed,
    )
    if args.smoke:
        # one dataset version across the whole trace (append_every=3 >
        # burst count) so the repeated param pool demonstrably hits
        kw.update(requests=18, n_sites=2, n_items=10, burst=3, append_every=3)
    if args.slo:
        with open(args.slo) as fh:
            slo = json.load(fh)
        serial = run(**kw, fuse=False, out=None)
        report = run(**kw, fuse=not args.no_fuse, out=args.out)
        problems = check_slo(report, slo, serial_report=serial)
        if problems:
            print("# SLO gate FAILED:")
            for p in problems:
                print(f"#   - {p}")
            return 1
        print(f"# SLO gate passed ({args.slo}): p50/p95 bands, fairness, "
              "fusion, fused<=serial wall")
        return 0
    run(**kw, fuse=not args.no_fuse, out=args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
