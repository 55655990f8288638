"""GridRuntime — execute the paper's mining applications on real devices
through the simulated grid.

The paper's central measurement is the gap between what a grid workflow
engine *spends* (preparation, submission, staging) and what the mining
itself *costs*.  The seed repo modelled the grid side with canned numbers;
this runtime closes the loop: every ``workflow.dag.Job`` maps onto jitted
site-local compute (the Pallas ``kmeans_assign`` kernel for K-Means
sub-clustering, the Pallas ``support_count`` kernel for GFM phase-1 local
Apriori over bitmap TransactionDBs), the single synchronization runs as a
real ``all_gather`` under ``shard_map`` on a ``launch.mesh``-built device
mesh (pooled vmap fallback when the host has too few devices), and each
job's measured wall time feeds the engine's simulated clock via
``TimedResult`` — so reported overhead percentages are calibrated by real
kernels.

    rt = GridRuntime.for_sites(4)                  # mesh if >=4 devices
    run = rt.run_vclustering(jax.random.PRNGKey(0), xs)
    run.result.labels, run.report.overhead_pct(), run.sync_mode
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stats import SuffStats
from repro.core.vclustering import (
    MergeResult,
    VClusterConfig,
    merge_gathered,
)
from repro.launch.mesh import make_site_mesh
from repro.obs import span
from repro.workflow.registry import RunContext, get_workload
from repro.workflow.engine import Engine, RunReport
from repro.workflow.executor import ExecutionBackend
from repro.workflow.overhead import (
    GridModel,
    estimate_dag,
    estimate_stages_from_specs,
    overhead_pct,
)
from repro.workflow.placement import resolve_placement
from repro.workflow.sitejob import job_specs, merge_owner_times


def _backend_differs(backend: str | ExecutionBackend, engine: Engine) -> bool:
    """Whether a requested backend requires rebuilding the engine.  An
    instance is honored by IDENTITY (a configured BatchedBackend with a
    custom min_batch must not be silently dropped just because its name
    matches); a name is compared as a string — no throwaway instance."""
    if isinstance(backend, ExecutionBackend):
        return backend is not engine.backend
    return backend != engine.backend.name


@dataclass
class RuntimeRun:
    """One application run: the mining result, the engine's grid report,
    and the runtime's own per-job device-time measurements (the numbers
    that were fed into the simulated clock)."""

    result: Any
    report: RunReport
    measured: dict[str, float] = field(default_factory=dict)
    sync_mode: str = "pooled"  # how the single synchronization executed
    schedule: str = "staged"  # which engine scheduler executed the DAG
    placement: str = "fixed"  # which matchmaking policy placed the jobs
    backend: str = "inline"  # which execution backend ran the callables
    # multi-host ownership (multihost backend): how many jax.distributed
    # processes cooperated, and which grid sites THIS process executed —
    # None means the run was not partitioned (every job ran locally)
    n_processes: int = 1
    owned_sites: tuple | None = None
    # the analytical view of the DAG that was actually executed (deps,
    # bytes, the sites the policy actually chose, measured compute) —
    # feed to overhead.estimate_* or sitejob.replay_dag; the sweep
    # benchmark replays exactly these
    specs: list = field(default_factory=list)
    # analytical bounds (paper §5.2.2), calibrated by the measured job
    # times: per-job critical path (the async ideal) and the stage-barrier
    # formula (the staged ideal)
    estimated_s: float = 0.0
    estimated_staged_s: float = 0.0

    def est_overhead_pct(self) -> float:
        """Table 3's 'Estimated overhead': measured wall vs the analytical
        bound matching this run's schedule mode."""
        est = self.estimated_s if self.schedule == "async" else self.estimated_staged_s
        return overhead_pct(self.report.wall_s, est)


@dataclass
class FusedRun:
    """One request's slice of a cross-request fused run
    (:meth:`GridRuntime.run_many`): its own mining result, its share of
    the measured device compute (summed from the merged report's per-job
    times under this request's name prefix), and the shared
    :class:`RunReport` of the ONE engine invocation that served every
    member."""

    result: Any
    compute_s: float
    backend: str
    report: RunReport


class GridRuntime:
    """Maps SiteJobs from the core algorithms onto one grid scheduler.

    ``sync`` selects how the clustering synchronization runs:
      * "auto" (default): shard_map all_gather over a device mesh when one
        with a site-sized axis is available, else the pooled fallback;
      * "shard_map": require the mesh (raises without enough devices);
      * "pooled": force the single-device vmap-equivalent path.
    Both paths are bit-identical — the logical merge is deterministic on
    the gathered statistics (the paper's redundant "logical merging").
    """

    def __init__(
        self,
        engine: Engine | None = None,
        mesh=None,
        axis: str = "sites",
        sync: str = "auto",
        use_kernel: bool = True,
        count_backend: str = "kernel",
        schedule: str | None = None,
        placement: str | None = None,
        backend: str | ExecutionBackend | None = None,
    ):
        if sync not in ("auto", "shard_map", "pooled"):
            raise ValueError(f"unknown sync mode {sync!r}")
        # ``schedule`` / ``placement`` / ``backend`` thread the engine's
        # scheduler mode ("staged" | "async"), matchmaking policy
        # ("fixed" | "round_robin" | "random" | "greedy_eta") and
        # execution backend ("inline" | "batched" | "multihost") through
        # the runtime; None keeps the given engine's own settings (or the
        # Engine defaults) untouched.  A caller-supplied engine is never
        # mutated — a differing setting gets an equivalent engine.
        #
        # Runtime-built engines default to the BATCHED backend: the
        # conformance suite proves it bit-identical to inline, and fused
        # fan-out dispatch is the raw-speed win for wide grids.  Pass
        # ``backend="inline"`` (or an explicit engine) to restore the
        # per-job host loop.
        if engine is None:
            engine = Engine(
                model=GridModel(),
                overlap_prep=True,
                schedule=schedule or "staged",
                placement=placement or "fixed",
                backend=backend or "batched",
            )
        elif (
            (schedule is not None and engine.schedule != schedule)
            or (placement is not None and resolve_placement(engine.placement).name != placement)
            or (backend is not None and _backend_differs(backend, engine))
        ):
            engine = Engine(
                model=engine.model,
                faults=engine.faults,
                rescue_path=engine.rescue_path,
                overlap_prep=engine.overlap_prep,
                straggler_factor=engine.straggler_factor,
                schedule=schedule or engine.schedule,
                placement=placement if placement is not None else engine.placement,
                backend=backend if backend is not None else engine.backend,
                trace=engine.trace,
            )
        self.engine = engine
        self.mesh = mesh
        self.axis = axis
        self.sync = sync
        self.use_kernel = use_kernel
        self.count_backend = count_backend

    @classmethod
    def for_sites(cls, n_sites: int, **kw) -> "GridRuntime":
        """Runtime with a launch.mesh site mesh when the host has enough
        devices (otherwise mesh=None and the pooled path is used)."""
        return cls(mesh=make_site_mesh(n_sites, kw.get("axis", "sites")), **kw)

    # -- synchronization strategies -----------------------------------------

    def _cluster_sync(self, n_sites: int, cfg: VClusterConfig):
        """Returns (sync_fn, mode) for the merge job."""
        be = self.engine.backend
        partitioned = getattr(be, "partition_sites", False)
        if partitioned and hasattr(be, "ensure_initialized"):
            # bring the distributed runtime up BEFORE any jax backend
            # query: jax.distributed.initialize must precede the first
            # process_count()/devices() call in this process, and this
            # method runs ahead of Engine.run's own begin_run bring-up
            be.ensure_initialized()
        if partitioned and jax.process_count() > 1:
            # A site-PARTITIONED multi-host run executes the merge job on
            # ONE owning process, so its sync must not be a mesh-spanning
            # collective (a shard_map over the global mesh entered from a
            # single process would deadlock the other hosts).  The pooled
            # merge is bit-identical — the paper's redundant logical
            # merge — and the shipped result reaches every process.
            # (SPMD-redundant multi-process runs — partition_sites=False —
            # enter the collective from every process and keep shard_map.)
            if self.sync == "shard_map":
                raise RuntimeError(
                    "sync='shard_map' is not supported on a site-partitioned "
                    "multi-process runtime: the merge job executes on its "
                    "owning process only; use sync='pooled' (bit-identical "
                    "logical merge) or MultiHostBackend(partition_sites=False)"
                )
            return None, "pooled"
        mesh = self.mesh
        if self.sync != "pooled" and mesh is None:
            mesh = make_site_mesh(n_sites, self.axis)
        usable = (
            mesh is not None
            and self.axis in mesh.shape
            and mesh.shape[self.axis] == n_sites
        )
        if self.sync == "shard_map" and not usable:
            raise RuntimeError(
                f"shard_map sync requires a mesh with {self.axis}={n_sites} "
                f"(have {dict(mesh.shape) if mesh is not None else None})"
            )
        if self.sync == "pooled" or not usable:
            return None, "pooled"  # vcluster_site_jobs defaults to merge_gathered

        axis = self.axis

        def sync(per_site: SuffStats) -> MergeResult:
            # place each site's stat triple on its device; the body's
            # all_gather is the protocol's single communication, and the
            # replicated merge is the paper's redundant logical merge
            sharded = jax.device_put(per_site, NamedSharding(mesh, P(axis)))

            def body(st: SuffStats) -> MergeResult:
                st = SuffStats(sizes=st.sizes[0], centers=st.centers[0], sse=st.sse[0])
                gathered = jax.lax.all_gather(st, axis)  # (s, k, ...) tiny
                return merge_gathered(gathered, cfg)

            fn = jax.shard_map(
                body, mesh=mesh, in_specs=(P(axis),), out_specs=P(), check_vma=False
            )
            return fn(sharded)

        return sync, "shard_map"

    # -- applications --------------------------------------------------------

    def _finish_run(self, jobs, rep: RunReport, result, measured, sync_mode: str) -> RuntimeRun:
        """Attach the measured-time-calibrated analytical bounds to a run.
        The specs carry the sites the placement policy ACTUALLY chose
        (``rep.placements``), so the bounds price the executed assignment
        rather than the builders' pre-assigned sites."""
        if rep.owned_jobs is not None:
            # partitioned (multi-host) run: this process only measured its
            # OWNED jobs — complete the record with the owner-measured
            # times the engine ledgered from shipped results, so
            # job_specs(strict=True) and the estimators see one
            # owner-authoritative time per job on every process
            measured = merge_owner_times(measured, rep.job_times, rep.owned_jobs)
        specs = job_specs(jobs, rep.job_times)
        if rep.placements:
            specs = [sp._replace(site=rep.placements.get(sp.name, sp.site)) for sp in specs]
        model = self.engine.model
        return RuntimeRun(
            result=result,
            report=rep,
            measured=measured,
            sync_mode=sync_mode,
            schedule=rep.schedule,
            placement=rep.placement,
            backend=rep.backend,
            n_processes=rep.n_processes,
            owned_sites=rep.owned_sites,
            specs=specs,
            estimated_s=estimate_dag(specs, model),
            estimated_staged_s=estimate_stages_from_specs(specs, model),
        )

    def run(self, app: str, data, params: dict | None = None) -> RuntimeRun:
        """Run ANY registered grid workload: the registry's
        :class:`~repro.workflow.registry.WorkloadSpec` resolves the params,
        builds the SiteJob DAG and names the terminal job; this method
        supplies the runtime context (count backend, kernel toggle, sync
        strategy) and the engine.  The ``run_vclustering``/``run_gfm``/
        ``run_fdm`` methods are thin wrappers over this — a workload
        registered through the registry needs NO runtime change."""
        spec = get_workload(app)
        if spec.runner != "grid":
            raise ValueError(
                f"app {app!r} is a {spec.runner!r} workload, not a grid DAG; "
                "serve it through launch.serve.MiningService"
            )
        p = spec.resolve(params)
        measured: dict[str, float] = {}
        ctx = RunContext(
            measured=measured,
            count_backend=self.count_backend,
            use_kernel=self.use_kernel,
            cluster_sync=self._cluster_sync,
        )
        with span("repro.build"):
            jobs, mode = spec.build_jobs(data, p, ctx)
        rep, results = self.engine.run_site_jobs(jobs, name=spec.name)
        return self._finish_run(jobs, rep, results[spec.terminal], measured, mode)

    def run_many(self, app: str, datas: list, params_list: list) -> list[FusedRun]:
        """Run SEVERAL same-app requests as ONE engine invocation — the
        cross-request batching seam the serving layer dispatches through.

        Each request's SiteJob DAG is built independently (its own
        resolved params, its own closures/ledgers) and merged into one
        job list under a ``r{j}/`` name prefix; ``batch_key``s are left
        UNPREFIXED, so same-shape fan-out jobs from different requests
        land in the same wave groups and the batched backend executes
        them as one fused dispatch (the builders' batch args carry every
        request-specific value — thresholds, PRNG keys, delta states —
        so the first member's closure can serve the whole merged group).
        The caller is responsible for only merging requests whose
        workload reports the same ``exec_batch_key`` signature; anything
        that varies job shapes or jit-static arguments must stay in
        separate calls.

        Returns one :class:`FusedRun` per request, in order: its own
        terminal result plus its measured device-compute share (the sum
        of the merged report's per-job times under its prefix — the same
        apportioning ``timed_batch`` does per job within a fused group).
        """
        spec = get_workload(app)
        if spec.runner != "grid":
            raise ValueError(
                f"app {app!r} is a {spec.runner!r} workload, not a grid DAG; "
                "serve it through launch.serve.MiningService"
            )
        if len(datas) != len(params_list):
            raise ValueError(
                f"run_many: {len(datas)} datasets vs {len(params_list)} param sets"
            )
        all_jobs: list = []
        modes: list[str] = []
        for j, (data, params) in enumerate(zip(datas, params_list)):
            p = spec.resolve(params)
            ctx = RunContext(
                measured={},
                count_backend=self.count_backend,
                use_kernel=self.use_kernel,
                cluster_sync=self._cluster_sync,
            )
            with span("repro.build"):
                jobs, mode = spec.build_jobs(data, p, ctx)
            modes.append(mode)
            prefix = f"r{j}/"
            for job in jobs:
                job.name = prefix + job.name
                job.deps = [prefix + d for d in job.deps]
            all_jobs.extend(jobs)
        if len(set(modes)) > 1:
            raise RuntimeError(
                f"run_many: requests resolved to different sync modes {modes}"
            )
        rep, results = self.engine.run_site_jobs(
            all_jobs, name=f"{spec.name}x{len(datas)}"
        )
        outs: list[FusedRun] = []
        for j in range(len(datas)):
            prefix = f"r{j}/"
            compute = sum(
                t for name, t in rep.job_times.items() if name.startswith(prefix)
            )
            outs.append(
                FusedRun(
                    result=results[prefix + spec.terminal],
                    compute_s=compute,
                    backend=rep.backend,
                    report=rep,
                )
            )
        return outs

    def run_vclustering(
        self, key: jax.Array, xs, cfg: VClusterConfig | None = None
    ) -> RuntimeRun:
        """Algorithm 1 end-to-end: per-site K-Means (Pallas assignment
        kernel by default) -> all_gather + logical merge -> per-site border
        perturbation, scheduled through the grid engine."""
        if cfg is None:
            cfg = VClusterConfig(use_kernel=self.use_kernel)
        return self.run("vclustering", xs, {"key": key, "cfg": cfg})

    def run_gfm(
        self, sites, k: int, minsup: float, local_minsup: float | None = None
    ) -> RuntimeRun:
        """Algorithm 2 end-to-end: per-site local Apriori (Pallas support
        counting by default), then the single 2-pass synchronization and
        top-down descent, scheduled through the grid engine."""
        return self.run(
            "gfm", sites, {"k": k, "minsup": minsup, "local_minsup": local_minsup}
        )

    def run_fdm(self, sites, k: int, minsup: float) -> RuntimeRun:
        """FDM baseline through the same scheduler (k level-synchronous
        rounds) — the comparison the paper draws against GFM."""
        return self.run("fdm", sites, {"k": k, "minsup": minsup})
