"""Overhead-sweep study: site-count x link-matrix x compute-scale x
schedule-mode x placement-policy over both mining applications, with
real-kernel-calibrated job times.

This reproduces the paper's Table 3 measured-vs-estimated overhead
comparison (the 295 s DAGMan preparation, serial per-job matchmaking and
Table 2 staging dominating cheap mining workflows) and quantifies how
much of that overhead the event-driven ``schedule="async"`` engine
recovers by overlapping submission with computation — the optimisation
the paper suggests ("partly overlapped by computations in the DAG") —
in the style of the companion study arXiv:1903.03008's site-count sweeps.

The placement axis runs the async scheduler under every matchmaking
policy (``fixed`` a-priori sites vs ``round_robin`` / seeded ``random``
/ ``greedy_eta`` adaptive placement); the ``skewed`` link variant
(per-site degraded Table 2 matrix + heterogeneous per-site compute
speeds, ``GridModel.skewed()``) is the scenario where matchmaking
dominates (arXiv:1412.2673), and the CI gate requires ``greedy_eta``
wall <= ``fixed`` wall there.  Staged cells keep fixed placement — they
are the Table 3 reproduction.

Methodology: each (application, site count) point is CALIBRATED by one
real run through ``GridRuntime`` (jitted site-local compute; per-job
device times recorded), then every links x schedule x placement cell
REPLAYS the captured DAG and measured times through the engine
deterministically.  Replaying isolates the scheduling policy — identical
DAG, model and job times across cells, zero timing noise — so
staged-vs-async and fixed-vs-adaptive deltas are exact and the CI
regression gate is stable across hosts.

The execution-backend axis calibrates each (app, site count) point
twice — once per ``workflow.executor`` backend: ``inline`` (one
dispatch per job; the full links x placement product and the Table 3
reproduction) and ``batched`` (each fan-out fused into ONE vmapped
dispatch, measured batch time apportioned per job; replayed on the
canonical grid5000/fixed cells).  The ``backend_comparisons`` block
pairs the two per (app, n_sites, schedule, scale); the CI gate requires
batched wall <= inline wall on the >=8-site fan-out-heavy cells.

Writes ``BENCH_sweep.json``::

    {"meta":  {...},
     "cells": [{"app", "n_sites", "links", "schedule", "placement",
                "exec_backend", "wall_s", "compute_s",
                "critical_compute_s", "critical_transfer_s", "prep_s",
                "submit_s", "transfer_s", "overhead_pct", "estimated_s",
                "estimated_staged_s", "est_overhead_pct", "n_jobs"}, ...],
     "comparisons": [{"app", "n_sites", "links", "wall_staged_s",
                      "wall_async_s", "recovered_s",
                      "recovered_pct_of_overhead"}, ...],
     "placement_comparisons": [{"app", "n_sites", "links",
                                "compute_scale", "wall_fixed_s",
                                "wall_greedy_eta_s", "recovered_s"}, ...],
     "backend_comparisons": [{"app", "n_sites", "links", "schedule",
                              "compute_scale", "wall_inline_s",
                              "wall_batched_s",
                              "critical_compute_inline_s",
                              "critical_compute_batched_s",
                              "recovered_s"}, ...],
     "table3":  [{"app", "n_sites", "measured_s", "estimated_s",
                  "est_overhead_pct"}, ...]}

The engine runs the paper-faithful configuration (full preparation
latency, serial matchmaking: ``overlap_prep=False``), so the staged
grid5000 cells ARE the Table 3 reproduction and the async cells show the
recovery.

    PYTHONPATH=src python -m benchmarks.bench_sweep [--smoke] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import platform

import jax

from benchmarks.common import row
from repro.workflow.overhead import overhead_pct
from repro.workflow.placement import POLICIES

LINK_VARIANTS = ("grid5000", "lan", "skewed")
SCHEDULES = ("staged", "async")
# the placement axis applies to the async scheduler (matchmaking is what
# the event-driven engine models); staged cells pin placement="fixed"
PLACEMENTS = POLICIES  # ("fixed", "round_robin", "random", "greedy_eta")
# execution-backend axis: which backend CALIBRATED the job times that a
# cell replays.  "inline" is the one-dispatch-per-job host loop (the
# full axis product — and the bit-for-bit continuation of pre-backend
# baselines); "batched" fuses each fan-out into one vmapped dispatch and
# replays on the canonical grid5000/fixed cells, where the CI gate
# requires batched wall <= inline wall on the >=8-site fan-outs
EXEC_BACKENDS = ("inline", "batched")
# what-if compute scaling of the calibrated job times (sim_compute_s
# replay): x1 is the paper's cheap-mining regime where overheads dominate
# and there is nothing to overlap; larger factors approach paper-scale
# datasets where the async engine's submit/compute overlap pays off
COMPUTE_SCALES = (1, 50)
COMPUTE_SCALES_FULL = (1, 10, 100)


def _cell(
    rep,
    app: str,
    n_sites: int,
    links: str,
    scale: int,
    est_dag: float,
    est_staged: float,
    exec_backend: str = "inline",
) -> dict:
    est = est_dag if rep.schedule == "async" else est_staged
    return {
        "app": app,
        "n_sites": n_sites,
        "links": links,
        "compute_scale": scale,
        "schedule": rep.schedule,
        "placement": rep.placement,
        "exec_backend": exec_backend,
        "wall_s": rep.wall_s,
        "compute_s": rep.compute_s,
        "critical_compute_s": rep.critical_compute_s,
        "critical_transfer_s": rep.critical_transfer_s,
        "prep_s": rep.prep_s,
        "submit_s": rep.submit_s,
        "transfer_s": rep.transfer_s,
        "overhead_pct": rep.overhead_pct(),
        "estimated_s": est_dag,
        "estimated_staged_s": est_staged,
        "est_overhead_pct": overhead_pct(rep.wall_s, est),
        "n_jobs": len(rep.job_times),
    }


def run(smoke: bool = False, out: str = "BENCH_sweep.json", use_kernel: bool | None = None) -> dict:
    from repro.core.apriori import TransactionDB
    from repro.core.vclustering import VClusterConfig
    from repro.data.synthetic import (
        gaussian_mixture,
        ibm_transactions,
        split_sites,
        split_transactions,
    )
    from repro.runtime import GridRuntime
    from repro.workflow.engine import Engine
    from repro.workflow.overhead import (
        GridModel,
        estimate_dag,
        estimate_stages_from_specs,
    )
    from repro.workflow.sitejob import replay_dag

    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"

    # 8 sites is the fan-out-heavy point the batched-vs-inline backend
    # gate runs on, so even the smoke sweep carries it
    site_counts = [2, 4, 8]
    if smoke:
        n_pts, dim, k_local, iters = 1200, 2, 6, 10
        n_tx, n_items, k_items, minsup = 800, 24, 3, 0.1
    else:
        n_pts, dim, k_local, iters = 8000, 4, 8, 15
        n_tx, n_items, k_items, minsup = 4000, 40, 3, 0.05

    pts, _ = gaussian_mixture(0, n_pts, dim, 4, spread=12.0, sigma=0.6)
    dense = ibm_transactions(seed=2, n_tx=n_tx, n_items=n_items, avg_tx_len=8, n_patterns=10)
    backend = "kernel" if use_kernel else "jnp"
    cfg = VClusterConfig(k_local=k_local, kmeans_iters=iters, use_kernel=use_kernel)

    def run_app(app: str, n_sites: int, rt: GridRuntime):
        if app == "vclustering":
            xs = split_sites(pts, n_sites, seed=1)
            return rt.run_vclustering(jax.random.PRNGKey(0), xs, cfg)
        sites = [TransactionDB.from_dense(s) for s in split_transactions(dense, n_sites, seed=0)]
        return rt.run_gfm(sites, k_items, minsup)

    def calibrate(app: str, n_sites: int, exec_backend: str = "inline"):
        """One real run: jitted site-local compute, per-job device times.
        A throwaway warm-up first so JIT compilation does not pollute the
        measurement.  The returned specs are the DAG the runtime actually
        executed (``RuntimeRun.specs``), measured times included —
        ``exec_backend`` selects HOW the fan-outs executed (inline host
        loop vs one fused vmapped dispatch with apportioned times)."""
        def fresh():
            return GridRuntime(
                engine=Engine(model=GridModel(), overlap_prep=True, backend=exec_backend),
                sync="pooled", use_kernel=use_kernel, count_backend=backend,
            )

        run_app(app, n_sites, fresh())  # warm-up (compilation)
        return run_app(app, n_sites, fresh()).specs

    scales = COMPUTE_SCALES if smoke else COMPUTE_SCALES_FULL
    cells: list[dict] = []
    comparisons: list[dict] = []
    placement_comparisons: list[dict] = []
    backend_comparisons: list[dict] = []
    for app in ("vclustering", "gfm"):
        for n_sites in site_counts:
            specs_by = {be: calibrate(app, n_sites, be) for be in EXEC_BACKENDS}
            for links in LINK_VARIANTS:
                # "skewed" is the heterogeneous grid: degraded per-site
                # links AND per-site compute speeds — the matchmaking
                # scenario the placement gate runs on
                model = GridModel.skewed() if links == "skewed" else GridModel(links=links)
                for scale in scales:
                    per_schedule: dict[str, dict] = {}
                    per_placement: dict[str, dict] = {}
                    per_backend: dict[tuple[str, str], dict] = {}
                    for exec_backend in EXEC_BACKENDS:
                        # the full links x placement product runs on the
                        # inline calibration (the Table 3 reproduction and
                        # the pre-backend baseline continuation); batched
                        # cells replay the canonical grid5000/fixed point,
                        # where the backend gate compares the two
                        if exec_backend != "inline" and links != "grid5000":
                            continue
                        scaled = [
                            sp._replace(compute_s=sp.compute_s * scale)
                            for sp in specs_by[exec_backend]
                        ]
                        for schedule in SCHEDULES:
                            # the placement axis applies to async (the
                            # matchmaker); staged is the Table 3 reproduction
                            placements = (
                                PLACEMENTS
                                if schedule == "async" and exec_backend == "inline"
                                else ("fixed",)
                            )
                            for placement in placements:
                                # deterministic replay: paper-faithful grid
                                # (full DAGMan prep, serial matchmaking),
                                # calibrated times
                                eng = Engine(
                                    model=model,
                                    overlap_prep=False,
                                    schedule=schedule,
                                    placement=placement,
                                )
                                rep = eng.run(replay_dag(scaled))
                                # bounds priced at the sites the policy chose
                                placed = [
                                    sp._replace(site=rep.placements.get(sp.name, sp.site))
                                    for sp in scaled
                                ]
                                est_dag = estimate_dag(placed, model)
                                est_staged = estimate_stages_from_specs(placed, model)
                                cell = _cell(
                                    rep, app, n_sites, links, scale, est_dag, est_staged,
                                    exec_backend,
                                )
                                cells.append(cell)
                                if exec_backend == "inline" and placement == "fixed":
                                    per_schedule[schedule] = cell
                                if exec_backend == "inline" and schedule == "async":
                                    per_placement[placement] = cell
                                if placement == "fixed":
                                    per_backend[(schedule, exec_backend)] = cell
                                row(
                                    f"sweep_{app}_s{n_sites}_{links}_x{scale}"
                                    f"_{schedule}_{placement}_{exec_backend}",
                                    cell["wall_s"],
                                    f"overhead={cell['overhead_pct']:.1f}%;"
                                    f"est={cell['estimated_s']:.2f}s",
                                )
                    staged, async_ = per_schedule["staged"], per_schedule["async"]
                    recovered = staged["wall_s"] - async_["wall_s"]
                    overhead = staged["wall_s"] - staged["estimated_staged_s"]
                    comparisons.append(
                        {
                            "app": app,
                            "n_sites": n_sites,
                            "links": links,
                            "compute_scale": scale,
                            "wall_staged_s": staged["wall_s"],
                            "wall_async_s": async_["wall_s"],
                            "recovered_s": recovered,
                            "recovered_pct_of_overhead": (
                                100.0 * recovered / overhead if overhead > 0 else 0.0
                            ),
                        }
                    )
                    fixed, greedy = per_placement["fixed"], per_placement["greedy_eta"]
                    placement_comparisons.append(
                        {
                            "app": app,
                            "n_sites": n_sites,
                            "links": links,
                            "compute_scale": scale,
                            "wall_fixed_s": fixed["wall_s"],
                            "wall_greedy_eta_s": greedy["wall_s"],
                            "recovered_s": fixed["wall_s"] - greedy["wall_s"],
                        }
                    )
                    if links == "grid5000":
                        # fused site-compute vs the host loop, identical
                        # grid model and topology: the wall delta is pure
                        # calibrated-compute difference (the CI gate
                        # requires batched <= inline on >=8-site cells)
                        for schedule in SCHEDULES:
                            icell = per_backend[(schedule, "inline")]
                            bcell = per_backend[(schedule, "batched")]
                            backend_comparisons.append(
                                {
                                    "app": app,
                                    "n_sites": n_sites,
                                    "links": links,
                                    "schedule": schedule,
                                    "compute_scale": scale,
                                    "wall_inline_s": icell["wall_s"],
                                    "wall_batched_s": bcell["wall_s"],
                                    "critical_compute_inline_s": icell["critical_compute_s"],
                                    "critical_compute_batched_s": bcell["critical_compute_s"],
                                    "recovered_s": icell["wall_s"] - bcell["wall_s"],
                                }
                            )

    # Table 3 reproduction: the paper's measured-vs-estimated overhead at
    # its own scale point (grid5000 links, unscaled compute, staged)
    table3 = [
        {
            "app": c["app"],
            "n_sites": c["n_sites"],
            "measured_s": c["wall_s"],
            "estimated_s": c["estimated_staged_s"],
            "est_overhead_pct": c["est_overhead_pct"],
        }
        for c in cells
        if c["links"] == "grid5000"
        and c["schedule"] == "staged"
        and c["compute_scale"] == 1
        and c["exec_backend"] == "inline"
    ]

    payload = {
        "meta": {
            "smoke": smoke,
            "backend": backend,
            "n_devices": len(jax.devices()),
            "jax_backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "site_counts": site_counts,
            "links": list(LINK_VARIANTS),
            "schedules": list(SCHEDULES),
            "placements": list(PLACEMENTS),
            "exec_backends": list(EXEC_BACKENDS),
            "compute_scales": list(scales),
            "clustering_shape": [n_pts, dim, k_local],
            "itemsets_shape": [n_tx, n_items, k_items, minsup],
        },
        "cells": cells,
        "comparisons": comparisons,
        "placement_comparisons": placement_comparisons,
        "backend_comparisons": backend_comparisons,
        "table3": table3,
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
    ap.add_argument("--smoke", action="store_true", help="tiny shapes + fewer site counts for CI")
    ap.add_argument("--out", default="BENCH_sweep.json")
    ap.add_argument(
        "--kernel",
        choices=["auto", "on", "off"],
        default="auto",
        help="Pallas kernels: auto = TPU only (interpret mode is too slow to sweep on CPU)",
    )
    args = ap.parse_args()
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run(
        smoke=args.smoke,
        out=args.out,
        use_kernel=None if args.kernel == "auto" else args.kernel == "on",
    )


if __name__ == "__main__":
    main()
