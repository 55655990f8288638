"""Collective-count comparison: per-job vs wave-fused result shipping.

The paper attributes the dominant grid overhead to per-job communication
rounds; the multihost backend's wave-fused shipping collapses them from
O(jobs) to O(ready waves).  This bench makes that reduction visible in
every PR's CI logs: each conformance app x schedule cell runs twice
through a force-partitioned ``MultiHostBackend`` (single process, the
collectives degenerate to identity — the LEDGER is what's measured, and
it counts shipments identically to a real process group), once with
``fuse_waves=False`` (PR-5 per-job rounds) and once with the wave-fused
default, and the shipment counts print side by side.

    PYTHONPATH=src python -m benchmarks.bench_collectives --sites 8
"""

from __future__ import annotations

import argparse
import json

from repro.runtime.backends import MultiHostBackend
from repro.runtime.conformance import APPS, SCHEDULES, run_app


def run(n_sites: int = 8, out: str | None = None) -> dict:
    report = {"n_sites": n_sites, "cells": []}
    print(f"# collective rounds per run, {n_sites} sites (per-job vs wave-fused shipping)")
    print("app,schedule,jobs,shipments_per_job,shipments_per_wave,waves,reduction_pct")
    for app in APPS:
        for schedule in SCHEDULES:
            counts: dict[str, dict] = {}
            for mode, fuse in (("per_job", False), ("per_wave", True)):
                be = MultiHostBackend(force_partition=True, fuse_waves=fuse)
                rr = run_app(app, n_sites, schedule, be)
                counts[mode] = dict(be.ledger(), waves=int(be.waves), jobs=len(rr.report.job_times))
            pj = counts["per_job"]["shipments"]
            pw = counts["per_wave"]["shipments"]
            cell = {
                "app": app,
                "schedule": schedule,
                "jobs": counts["per_job"]["jobs"],
                "shipments_per_job": pj,
                "shipments_per_wave": pw,
                "waves": counts["per_wave"]["waves"],
                "reduction_pct": 100.0 * (1 - pw / pj) if pj else 0.0,
            }
            report["cells"].append(cell)
            print(
                f"{app},{schedule},{cell['jobs']},{pj},{pw},{cell['waves']},"
                f"{cell['reduction_pct']:.0f}"
            )
    if out:
        try:
            with open(out, "w") as fh:
                json.dump(report, fh, indent=2, sort_keys=True)
        except FileNotFoundError:
            # name the missing directory and the fix instead of a bare
            # traceback — CI passes a relative path from the repo root
            raise SystemExit(
                f"bench_collectives: cannot write {out!r} — its directory does "
                f"not exist; create it (mkdir -p) or pass --out with an "
                f"existing directory"
            ) from None
        print(f"# wrote {out}")
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sites", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run(n_sites=args.sites, out=args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
