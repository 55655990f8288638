"""Workload plugin registry — the ONE seam every mining application
passes through.

The paper runs two applications (distributed clustering, frequent-itemset
generation) on one grid workflow engine; the framework-over-apps
direction of "Toward a Distributed Knowledge Discovery system for Grid
systems" (arXiv:1704.03538) is a *family* of workloads over the same
kernels.  Before this module the family was hand-wired twice — ``run_*``
methods on ``GridRuntime`` and an if/elif chain plus parallel app tuples
in ``launch.serve`` — which is exactly the drift surface where "unknown
app" checks, dataset-kind checks and param defaults disagree.

Now every workload registers ONE :class:`WorkloadSpec`:

  * identity — ``name``, ``dataset_kind`` ("transactions" | "points"),
    ``description``;
  * **param schema** — ``Param`` entries with kind, default and docs;
    the spec owns coercion (``resolve``) and submit-time validation
    (``validate_submitted``: unknown/internal keys and NON-FINITE floats
    are rejected before a request is admitted — the malformed-params
    crash class dies here, not in the dispatch loop);
  * **result schema** — ``result_fields`` plus a ``digest`` callable
    producing the canonical JSON-able form the cross-backend conformance
    suite compares bit-for-bit;
  * **how to run it** — grid workloads provide ``build_jobs`` (SiteJob
    DAG + sync mode, consumed by ``GridRuntime.run``); local
    (delta-served) workloads provide ``local_fn`` (+ optional
    ``finalize``).  Neither sees the service: a grid workload gets its
    per-site data from ``workflow.datasets.Dataset.sites`` (the split
    its ``dataset_kind`` implies) and the resolved params unchanged, and
    a local one gets the :class:`~repro.workflow.datasets.Dataset`;
  * **smoke params** — the canonical small-param points the service
    trace, the CI smoke and the registry-driven tests exercise.

Consumers are table-driven off this registry and NOTHING else:
``GridRuntime.run(app, ...)``, ``MiningService`` submit validation and
``_execute`` dispatch, ``runtime.conformance`` (apps, digests, job maps)
and the benches.  Registering a spec here is the WHOLE integration —
``cd_apriori`` (count-distribution Apriori, arXiv:1903.03008) and
``topk`` (streaming top-k frequent itemsets over the delta path) land
through this seam alone, as the proof.

``tools/check_registry.py`` and ``tests/test_registry.py`` run
:func:`validate_registry`, so an under-specified plugin fails CI — not a
tenant request.
"""

from __future__ import annotations

import math
from collections.abc import Callable
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

DATASET_KINDS = ("transactions", "points")
RUNNERS = ("grid", "local")
PARAM_KINDS = ("int", "float", "str", "bool", "any")


@dataclass(frozen=True)
class Param:
    """One entry of a workload's param schema.

    ``kind`` drives coercion (``int``/``float``/``str``/``bool``, or
    ``any`` for pass-through); ``default`` is installed by ``resolve``
    (None means "no value" — adapters substitute a context-dependent
    default, e.g. the service's ``n_sites``); ``internal`` params carry
    non-JSON values (PRNG keys, config objects) between runtime wrappers
    and builders and are REJECTED at service submit."""

    name: str
    kind: str = "any"
    default: Any = None
    doc: str = ""
    internal: bool = False

    def coerce(self, v: Any) -> Any:
        if v is None or self.kind == "any":
            return v
        try:
            if self.kind == "int":
                # bool is an int subclass; floats must be integral, not
                # truncated ("n_sites": 2.5 is a mistake, not 2)
                if isinstance(v, float) and (not math.isfinite(v) or v != int(v)):
                    raise ValueError(f"expected an integer, got {v!r}")
                return int(v)
            if self.kind == "float":
                return float(v)
            if self.kind == "bool":
                return bool(v)
            return str(v)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"param {self.name!r} expects {self.kind}, got {v!r} ({e})"
            ) from None


def _reject_nonfinite(name: str, v: Any) -> None:
    """Recursively reject non-finite floats in a submitted param value —
    ``params_key`` is total over them (the backstop), but a request
    carrying inf/nan minsup is malformed and must be a ledgered
    rejection, not a queued execution."""
    if isinstance(v, float) and not math.isfinite(v):
        raise ValueError(f"param {name!r} is non-finite ({v!r}); rejected at submit")
    if isinstance(v, dict):
        for k, x in v.items():
            _reject_nonfinite(f"{name}.{k}", x)
    elif isinstance(v, (list, tuple, set, frozenset)):
        for x in v:
            _reject_nonfinite(name, x)


@dataclass(frozen=True)
class RunContext:
    """What a ``build_jobs`` builder or a ``local_fn`` may use from its
    host runtime (``GridRuntime.context``): the measured-times dict the
    jobs feed, the support-count backend, the kernel toggle, and
    (clustering) the runtime's sync-strategy factory
    ``cluster_sync(n_sites, cfg) -> (sync_fn | None, mode)``."""

    measured: dict = field(default_factory=dict)
    count_backend: str = "kernel"
    use_kernel: bool = True
    cluster_sync: Callable | None = None


@dataclass(frozen=True)
class WorkloadSpec:
    """Everything the framework needs to know about one mining workload.

    Grid workloads (``runner="grid"``) run a SiteJob DAG through
    ``GridRuntime.run``: ``build_jobs(data, params, ctx)`` returns
    ``(jobs, sync_mode)`` and ``terminal`` names the job whose result is
    the run's result; the service calls it on ``Dataset.sites`` with the
    resolved params.  Local workloads (``runner="local"``) are served
    in-process from per-dataset incremental state:
    ``local_fn(ds, params, ctx)`` returns the zero-arg callable the
    service ledgers as a single-job DAG; ``finalize(ds, params, value)``
    optionally folds the result back into dataset state (k-means
    warm-start centroids).

    ``exec_batch_key(ds, params)`` is the CROSS-REQUEST batching opt-in:
    given the dataset state and the resolved params (``n_sites``
    substituted by the service), it returns a hashable signature — two
    execution groups in the same service wave whose workloads report the
    SAME signature (same app, dataset, version, and the same signature
    tuple) run as ONE fused dispatch through the batched backend's
    ``batch_key`` machinery, with measured device time apportioned per
    request.  The signature must pin every value that changes job
    shapes, jit-static arguments, or DAG structure (``k`` levels,
    ``n_sites``/``split_seed``, ``k_local``/``iters``); only params the
    builders accept per-member (thresholds, seeds) may be left out.
    ``None`` (the default, and a valid return value) means the workload
    NEVER fuses across requests — e.g. ``kmeans``, whose warm-start
    ``finalize`` makes serial wave order observable."""

    name: str
    dataset_kind: str  # "transactions" | "points"
    runner: str  # "grid" | "local"
    description: str
    params: tuple[Param, ...]
    result_fields: tuple[str, ...]
    digest: Callable[[Any], dict]
    # grid runner pieces
    build_jobs: Callable | None = None
    terminal: str = "collect"
    # local runner pieces
    local_fn: Callable | None = None
    finalize: Callable | None = None
    # cross-request batching opt-in: (ds, resolved_params) -> hashable
    # signature, or None to never fuse (see class docstring)
    exec_batch_key: Callable | None = None
    smoke_params: tuple[dict, ...] = ()
    conformance: bool = False  # part of the cross-backend conformance matrix

    def schema(self) -> dict[str, Param]:
        return {p.name: p for p in self.params}

    def public_params(self) -> tuple[Param, ...]:
        return tuple(p for p in self.params if not p.internal)

    def resolve(self, params: dict | None) -> dict:
        """Defaults + coercion over the full schema (internal params
        allowed) — what builders and executors consume.  Unknown keys
        raise: every consumer shares one param vocabulary."""
        out = {p.name: p.default for p in self.params}
        sch = self.schema()
        for k, v in (params or {}).items():
            if k not in sch:
                raise ValueError(
                    f"app {self.name!r} has no param {k!r}; "
                    f"known params: {tuple(sch)}"
                )
            out[k] = sch[k].coerce(v)
        return out

    def validate_submitted(self, params: dict | None) -> dict:
        """Submit-time validation: the coerced copy of exactly the keys
        the tenant sent.  Rejects unknown keys, internal-only keys, and
        non-finite numerics — with a ValueError naming the offender."""
        sch = self.schema()
        out: dict = {}
        for k, v in (params or {}).items():
            p = sch.get(str(k))
            if p is None or p.internal:
                public = tuple(q.name for q in self.public_params())
                raise ValueError(
                    f"app {self.name!r} does not accept param {k!r}; "
                    f"accepted params: {public}"
                )
            _reject_nonfinite(p.name, v)
            out[p.name] = p.coerce(v)
        return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, WorkloadSpec] = {}


def register(spec: WorkloadSpec) -> WorkloadSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"workload {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def workloads() -> tuple[WorkloadSpec, ...]:
    """Every registered spec, in registration order."""
    return tuple(_REGISTRY.values())


def app_names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def conformance_apps() -> tuple[str, ...]:
    """The apps in the cross-backend conformance matrix (grid workloads
    whose digests must be bit-identical across execution backends)."""
    return tuple(s.name for s in _REGISTRY.values() if s.conformance)


def get_workload(name: str) -> WorkloadSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown app {name!r}; expected one of {app_names()}"
        ) from None


def validate_registry() -> list[str]:
    """Every registered workload must be fully specified — the CI check
    (``tools/check_registry.py``) that makes an under-specified plugin a
    build failure instead of a tenant-visible crash.  Returns
    human-readable problems (empty = clean)."""
    problems: list[str] = []
    for spec in _REGISTRY.values():
        where = f"workload {spec.name!r}"
        if not spec.name:
            problems.append("workload with empty name")
        if spec.dataset_kind not in DATASET_KINDS:
            problems.append(f"{where}: bad dataset_kind {spec.dataset_kind!r}")
        if spec.runner not in RUNNERS:
            problems.append(f"{where}: bad runner {spec.runner!r}")
        if not spec.description:
            problems.append(f"{where}: missing description")
        if not spec.params:
            problems.append(f"{where}: declares no param schema")
        seen: set[str] = set()
        for p in spec.params:
            if p.kind not in PARAM_KINDS:
                problems.append(f"{where}: param {p.name!r} has bad kind {p.kind!r}")
            if not p.doc:
                problems.append(f"{where}: param {p.name!r} has no doc")
            if p.name in seen:
                problems.append(f"{where}: duplicate param {p.name!r}")
            seen.add(p.name)
        if not spec.result_fields:
            problems.append(f"{where}: declares no result schema (result_fields)")
        if not callable(spec.digest):
            problems.append(f"{where}: digest is not callable")
        if spec.runner == "grid":
            if not callable(spec.build_jobs):
                problems.append(f"{where}: grid workload missing build_jobs")
            if not spec.terminal:
                problems.append(f"{where}: grid workload missing terminal job name")
        else:
            if not callable(spec.local_fn):
                problems.append(f"{where}: local workload missing local_fn")
        if spec.exec_batch_key is not None and not callable(spec.exec_batch_key):
            problems.append(f"{where}: exec_batch_key must be callable or None")
        if not spec.smoke_params:
            problems.append(f"{where}: declares no smoke_params")
        for sp in spec.smoke_params:
            try:
                spec.validate_submitted(sp)
            except ValueError as e:
                problems.append(f"{where}: smoke params {sp!r} invalid: {e}")
    return problems


def app_table_markdown() -> str:
    """The registry as a markdown table — README/docs app tables are
    REGENERATED from this, never hand-edited."""
    lines = [
        "| App | Data | Runner | Params | Result |",
        "|---|---|---|---|---|",
    ]
    for s in workloads():
        params = ", ".join(
            f"`{p.name}`" + (f"={p.default}" if p.default is not None else "")
            for p in s.public_params()
        )
        result = ", ".join(f"`{f}`" for f in s.result_fields)
        lines.append(
            f"| `{s.name}` | {s.dataset_kind} | {s.runner} | {params} | {result} |"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Shared digest helpers
# ---------------------------------------------------------------------------


def comm_digest(comm) -> dict:
    """CommLog in canonical JSON-able form (conformance compares it
    bit-for-bit across execution backends and processes)."""
    return {
        "rounds": int(comm.rounds),
        "bytes_sent": int(comm.bytes_sent),
        "messages": int(comm.messages),
        "count_calls": int(comm.count_calls),
        "per_round_bytes": [int(b) for b in comm.per_round_bytes],
    }


def _frequent_digest(frequent: dict) -> dict:
    return {",".join(map(str, its)): int(c) for its, c in sorted(frequent.items())}


# ---------------------------------------------------------------------------
# The built-in workload family
# ---------------------------------------------------------------------------
#
# Each registration below is the COMPLETE integration of that workload:
# GridRuntime.run, MiningService, conformance and the benches all discover
# it from here.


_SPLIT_PARAMS = (
    Param("n_sites", "int", None, "sites to split the dataset across (service default)"),
    Param("split_seed", "int", 0, "seed for the site split"),
)

_MINE_PARAMS = (
    Param("k", "int", 3, "maximum itemset size"),
    Param("minsup", "float", 0.1, "global minimum support fraction"),
)


def _mine_exec_key(ds, p) -> tuple:
    """Threshold-only cross-request variation for the level-synchronous
    miners (fdm / gfm / cd_apriori): ``k`` pins the DAG depth and
    ``n_sites``/``split_seed`` pin the padded site shapes, so two groups
    sharing this signature differ only in support thresholds — which the
    builders' fused fan-outs accept per member."""
    return (p["k"], p["n_sites"], p["split_seed"])


# -- apriori (local, delta-served) ------------------------------------------


def _apriori_local(ds, p, ctx: RunContext):
    if p["min_count"] is not None:
        mc = p["min_count"]
    else:
        mc = max(1, int(math.ceil(p["minsup"] * ds.delta.n_tx)))
    return lambda: ds.delta.query(p["k"], mc)


def _delta_exec_key(ds, p) -> tuple:
    """Delta-served local workloads (apriori / topk) fuse UNconditionally:
    every param point is accepted per member, because the fused local
    path just invokes each group's callable in wave order inside one
    merged engine run — identical to the serial per-group path, with the
    shared delta state serving every member from one warm cache.  kmeans
    deliberately has NO hook: its warm-start finalize makes results
    depend on whether a sibling's centroids landed before the callable
    was built, so fusing would change (legitimately) order-visible
    output."""
    return ()


def _digest_localmine(r) -> dict:
    return {
        "counts": _frequent_digest(r.counts),
        "frequent": {
            str(lv): [",".join(map(str, its)) for its in sorted(r.frequent[lv])]
            for lv in sorted(r.frequent)
        },
    }


register(WorkloadSpec(
    name="apriori",
    dataset_kind="transactions",
    runner="local",
    description="incremental Apriori over the dataset's delta state "
                "(bit-identical to from-scratch mining of the stream)",
    params=(
        Param("k", "int", 3, "maximum itemset size"),
        Param("minsup", "float", 0.1, "minimum support fraction (ignored if min_count given)"),
        Param("min_count", "int", None, "absolute minimum count (overrides minsup)"),
    ),
    result_fields=("counts", "frequent", "count_calls", "candidates_counted"),
    digest=_digest_localmine,
    local_fn=_apriori_local,
    exec_batch_key=_delta_exec_key,
    smoke_params=({"k": 3, "minsup": 0.3}, {"k": 2, "minsup": 0.4}),
))


# -- gfm (grid) --------------------------------------------------------------


def _gfm_build(data, p, ctx: RunContext):
    from repro.core.gfm import gfm_site_jobs

    jobs = gfm_site_jobs(
        data, p["k"], p["minsup"],
        backend=ctx.count_backend,
        local_minsup=p["local_minsup"],
        measured=ctx.measured,
    )
    return jobs, "host"


def _digest_gfm(r) -> dict:
    return {
        "frequent": _frequent_digest(r.frequent),
        "comm": comm_digest(r.comm),
        "pool_sizes": [int(x) for x in r.pool_sizes],
        "n_total_tx": int(r.n_total_tx),
    }


register(WorkloadSpec(
    name="gfm",
    dataset_kind="transactions",
    runner="grid",
    description="the paper's Grid Frequent-itemset Mining: per-site local "
                "Apriori, ONE 2-pass synchronization, top-down descent",
    params=_MINE_PARAMS + (
        Param("local_minsup", "float", None, "per-site local support (default: minsup)"),
    ) + _SPLIT_PARAMS,
    result_fields=("frequent", "comm", "local", "pool_sizes", "n_total_tx"),
    digest=_digest_gfm,
    build_jobs=_gfm_build,
    terminal="decide",
    exec_batch_key=_mine_exec_key,
    smoke_params=({"k": 2, "minsup": 0.35}, {"k": 2, "minsup": 0.45}),
    conformance=True,
))


# -- fdm (grid) --------------------------------------------------------------


def _fdm_build(data, p, ctx: RunContext):
    from repro.core.fdm import fdm_site_jobs

    jobs = fdm_site_jobs(
        data, p["k"], p["minsup"], backend=ctx.count_backend, measured=ctx.measured
    )
    return jobs, "host"


def _digest_fdm(r) -> dict:
    return {
        "frequent": _frequent_digest(r.frequent),
        "comm": comm_digest(r.comm),
        "per_level_candidates": [int(c) for c in r.per_level_candidates],
    }


register(WorkloadSpec(
    name="fdm",
    dataset_kind="transactions",
    runner="grid",
    description="FDM baseline: k level-synchronous candidate/announce/"
                "remote-support rounds (the paper's comparison point)",
    params=_MINE_PARAMS + _SPLIT_PARAMS,
    result_fields=("frequent", "comm", "remote_count_time",
                   "total_count_time", "per_level_candidates"),
    digest=_digest_fdm,
    build_jobs=_fdm_build,
    terminal="collect",
    exec_batch_key=_mine_exec_key,
    smoke_params=({"k": 2, "minsup": 0.35}, {"k": 2, "minsup": 0.45}),
    conformance=True,
))


# -- cd_apriori (grid, registered THROUGH the seam) --------------------------


def _cd_build(data, p, ctx: RunContext):
    from repro.core.cdapriori import cd_site_jobs

    jobs = cd_site_jobs(
        data, p["k"], p["minsup"], backend=ctx.count_backend, measured=ctx.measured
    )
    return jobs, "host"


def _digest_cd(r) -> dict:
    return {
        "frequent": _frequent_digest(r.frequent),
        "comm": comm_digest(r.comm),
        "per_level_candidates": [int(c) for c in r.per_level_candidates],
        "n_total_tx": int(r.n_total_tx),
    }


register(WorkloadSpec(
    name="cd_apriori",
    dataset_kind="transactions",
    runner="grid",
    description="count-distribution Apriori (arXiv:1903.03008): every site "
                "counts the one shared candidate set, one count-vector "
                "exchange per level",
    params=_MINE_PARAMS + _SPLIT_PARAMS,
    result_fields=("frequent", "comm", "per_level_candidates", "n_total_tx"),
    digest=_digest_cd,
    build_jobs=_cd_build,
    terminal="collect",
    exec_batch_key=_mine_exec_key,
    smoke_params=({"k": 2, "minsup": 0.35}, {"k": 2, "minsup": 0.45}),
    conformance=True,
))


# -- topk (local, delta-served, registered THROUGH the seam) -----------------


def _topk_local(ds, p, ctx: RunContext):
    from repro.core.apriori import topk_itemsets

    return lambda: topk_itemsets(ds.delta, p["k"], p["top"], floor=p["floor"])


def _digest_topk(r) -> dict:
    return {
        "items": [[",".join(map(str, its)), int(c)] for its, c in r.items],
        "threshold": int(r.threshold),
        "k_max": int(r.k_max),
    }


register(WorkloadSpec(
    name="topk",
    dataset_kind="transactions",
    runner="local",
    description="streaming top-k frequent itemsets over the delta path "
                "(threshold-halving search, counts served from the cache)",
    params=(
        Param("k", "int", 3, "maximum itemset size"),
        Param("top", "int", 10, "how many itemsets to return"),
        Param("floor", "int", 1, "smallest support threshold the search may reach"),
    ),
    result_fields=("items", "threshold", "k_max", "count_calls"),
    digest=_digest_topk,
    local_fn=_topk_local,
    exec_batch_key=_delta_exec_key,
    smoke_params=({"k": 2, "top": 5}, {"k": 2, "top": 3}),
))


# -- kmeans (local, warm-started) -------------------------------------------


def _kmeans_local(ds, p, ctx: RunContext):
    from repro.core.kmeans import kmeans, kmeans_warm

    k, iters = p["k"], p["iters"]
    x = ds.points()
    warm = ds.warm_centers.get(k)
    if warm is not None:
        return lambda: kmeans_warm(x, warm, iters=iters, use_kernel=ctx.use_kernel)
    key = jax.random.PRNGKey(p["seed"])
    return lambda: kmeans(key, x, k, iters=iters, use_kernel=ctx.use_kernel)


def _kmeans_finalize(ds, p, value) -> None:
    ds.warm_centers[p["k"]] = np.asarray(value.centers)


def _digest_kmeans(r) -> dict:
    return {
        "assign": np.asarray(r.assign).astype(int).tolist(),
        "inertia": float(r.inertia),
    }


register(WorkloadSpec(
    name="kmeans",
    dataset_kind="points",
    runner="local",
    description="pooled K-Means, warm-started from the previous version's "
                "centroids after each append",
    params=(
        Param("k", "int", 3, "number of clusters"),
        Param("iters", "int", 25, "Lloyd iterations"),
        Param("seed", "int", 0, "PRNG seed for cold-start init"),
    ),
    result_fields=("centers", "assign", "inertia", "stats"),
    digest=_digest_kmeans,
    local_fn=_kmeans_local,
    finalize=_kmeans_finalize,
    smoke_params=({"k": 3, "iters": 10}, {"k": 4, "iters": 10}),
))


# -- vclustering (grid) ------------------------------------------------------


def _vcluster_build(data, p, ctx: RunContext):
    import jax.numpy as jnp

    from repro.core.vclustering import VClusterConfig, vcluster_site_jobs

    xs = jnp.asarray(data)
    cfg = p["cfg"]
    if cfg is None:
        cfg = VClusterConfig(
            k_local=p["k_local"], kmeans_iters=p["iters"], use_kernel=ctx.use_kernel
        )
    key = p["key"]
    if key is None:
        key = jax.random.PRNGKey(p["seed"])
    if ctx.cluster_sync is not None:
        sync, mode = ctx.cluster_sync(xs.shape[0], cfg)
    else:
        sync, mode = None, "pooled"
    jobs = vcluster_site_jobs(key, xs, cfg, sync=sync, measured=ctx.measured)
    return jobs, mode


def _vcluster_exec_key(ds, p) -> tuple | None:
    """``k_local``/``iters`` are jit-static in the site kernels and
    ``n_sites``/``split_seed`` pin the site shapes, so only the PRNG
    ``seed`` may vary across fused members (the cluster fan-out threads
    each member's key through its batch args).  Runtime callers passing
    explicit ``key``/``cfg`` objects never fuse — those are unhashable
    and bypass the seed/param schema entirely."""
    if p["key"] is not None or p["cfg"] is not None:
        return None
    return (p["k_local"], p["iters"], p["n_sites"], p["split_seed"])


def _digest_vclustering(r) -> dict:
    return {
        "labels": np.asarray(r.labels).astype(int).tolist(),
        "n_global": int(r.merged.n_global),
        "n_merges": int(r.merged.n_merges),
        "comm_bytes": int(r.comm_bytes),
    }


register(WorkloadSpec(
    name="vclustering",
    dataset_kind="points",
    runner="grid",
    description="the paper's Algorithm 1: per-site K-Means, all_gather + "
                "logical merge, border perturbation",
    params=(
        Param("k_local", "int", 8, "sub-clusters per site"),
        Param("iters", "int", 15, "K-Means iterations per site"),
        Param("seed", "int", 0, "PRNG seed"),
        Param("key", "any", None, "explicit jax PRNG key (runtime callers)", internal=True),
        Param("cfg", "any", None, "explicit VClusterConfig (runtime callers)", internal=True),
    ) + _SPLIT_PARAMS,
    result_fields=("labels", "merged", "comm_bytes"),
    digest=_digest_vclustering,
    build_jobs=_vcluster_build,
    terminal="collect",
    exec_batch_key=_vcluster_exec_key,
    smoke_params=({"k_local": 4, "iters": 8}, {"k_local": 4, "iters": 8, "seed": 1}),
    conformance=True,
))
