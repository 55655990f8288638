"""Variance-based distributed clustering — the paper's Algorithm 1.

Pipeline (per the paper):
  1. Each site i clusters its local data into k_i sub-clusters (K-Means).
  2. Sites ship ONLY sufficient statistics (N, center, SSE) — KB-scale.
  3. "Logical merge": greedily merge the sub-cluster pair with the smallest
     variance increase s(i,j) while the merged variance stays below a
     threshold (experiments: 2x the largest individual sub-cluster SSE).
     The merge is deterministic given the gathered stats, so EVERY site can
     run it redundantly and obtain the identical global labeling — no
     designated aggregator, no broadcast-back (the paper's "merging is
     'logical'" property).
  4. Border perturbation: each global cluster contributes b border
     candidates; a candidate moves to the closest other global cluster when
     the move lowers the global SSE.  Done site-locally on each site's own
     points (paper: "no additional communications are required").

Two drivers:
  * ``vcluster_pooled`` — reference semantics on a (s, n, D) stack of site
    datasets in one process (vmap over sites).  This is the oracle used by
    tests and by single-host examples.
  * ``vcluster_shard_map`` — the distributed path: shard_map over a mesh
    axis, ``lax.all_gather`` of the stat triples as the single
    communication, redundant logical merge per shard.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.kmeans import kmeans
from repro.core.stats import (
    SuffStats,
    merge_cost,
    stack_site_stats,
)


class VClusterConfig(NamedTuple):
    k_local: int = 20  # sub-clusters per site (paper experiments: 20)
    kmeans_iters: int = 25
    threshold_factor: float = 2.0  # tau = factor * max individual SSE
    # The paper's line 10 ("while var(C_i,C_j) < tau") is ambiguous between
    # the merged cluster's total variance and the *increase* s(i,j) ("s(i,j)
    # represents the increase in the variance while merging").  The
    # "increase" reading recovers planted structure (tests) and is the
    # default; "merged_var" is kept for the literal reading.
    criterion: str = "increase"  # "increase" (default) | "merged_var"
    border_candidates: int = 8  # b, per global cluster
    perturb_rounds: int = 1
    use_kernel: bool = False  # Pallas assignment kernel


class MergeResult(NamedTuple):
    labels: jax.Array  # (M,) int32 — root slot id per sub-cluster slot
    stats: SuffStats  # merged stats in root slots (dead slots size 0)
    n_merges: jax.Array  # () int32
    n_global: jax.Array  # () int32 — number of live global clusters


# ---------------------------------------------------------------------------
# Phase 2/3: logical merge over gathered sufficient statistics
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("criterion",))
def merge_subclusters(
    stats: SuffStats,
    threshold: jax.Array,
    criterion: str = "merged_var",
) -> MergeResult:
    """Greedy variance-constrained agglomeration over M sub-cluster slots.

    criterion "merged_var": merge while  sse_i + sse_j + s(i,j) < threshold
      (the paper's ``var(C_i, C_j) < tau``, tau = 2 x max individual SSE).
    criterion "increase":   merge while  s(i,j) < threshold.
    """
    m = stats.n_slots
    labels0 = jnp.arange(m, dtype=jnp.int32)

    def score(st: SuffStats) -> jax.Array:
        s = merge_cost(st)  # (M, M), inf on dead/diag
        if criterion == "merged_var":
            tot = st.sse[:, None] + st.sse[None, :]
            return jnp.where(jnp.isfinite(s), s + tot, jnp.inf)
        return s

    def cond(carry):
        st, labels, n_merges = carry
        sc = score(st)
        return jnp.min(sc) < threshold

    def body(carry):
        st, labels, n_merges = carry
        sc = score(st)
        flat = jnp.argmin(sc)
        i, j = flat // m, flat % m
        # merge j into i (paper's update formulas)
        ni, nj = st.sizes[i], st.sizes[j]
        ci, cj = st.centers[i], st.centers[j]
        n_new = ni + nj
        w = 1.0 / jnp.maximum(n_new, 1e-30)
        c_new = (ni * ci + nj * cj) * w
        s_ij = ni * nj * w * jnp.sum((ci - cj) ** 2)
        sse_new = st.sse[i] + st.sse[j] + s_ij
        st = SuffStats(
            sizes=st.sizes.at[i].set(n_new).at[j].set(0.0),
            centers=st.centers.at[i].set(c_new).at[j].set(0.0),
            sse=st.sse.at[i].set(sse_new).at[j].set(0.0),
        )
        labels = jnp.where(labels == labels[j], labels[i], labels)
        return st, labels, n_merges + 1

    st, labels, n_merges = jax.lax.while_loop(cond, body, (stats, labels0, jnp.int32(0)))
    n_global = jnp.sum((st.sizes > 0).astype(jnp.int32))
    return MergeResult(labels=labels, stats=st, n_merges=n_merges, n_global=n_global)


def paper_threshold(stats: SuffStats, factor: float) -> jax.Array:
    """tau = factor * max individual sub-cluster SSE (paper's setting)."""
    return factor * jnp.max(jnp.where(stats.sizes > 0, stats.sse, -jnp.inf))


def merge_gathered(per_site: SuffStats, cfg: VClusterConfig) -> MergeResult:
    """Logical merge over gathered per-site stats (s, k, ...) — the single
    deterministic computation every site runs redundantly after the one
    all_gather.  Shared by the pooled driver, the shard_map driver, and the
    runtime's sync job."""
    flat = stack_site_stats(per_site)
    tau = paper_threshold(flat, cfg.threshold_factor)
    return merge_subclusters(flat, tau, criterion=cfg.criterion)


# ---------------------------------------------------------------------------
# Phase 4: border perturbation (site-local, zero extra communication)
# ---------------------------------------------------------------------------


def perturb_site(
    x: jax.Array,  # (n, D) site-local points
    point_slot: jax.Array,  # (n,) int32 — sub-cluster SLOT id per point
    merged: MergeResult,
    b: int,
) -> tuple[jax.Array, SuffStats]:
    """Paper lines 13-24: move border candidates between global clusters when
    the global variance decreases.  Operates on this site's own points only,
    against the (replicated) global statistics; returns per-point global
    slot labels and this site's locally-updated copy of the global stats.

    Candidate selection: within each live global cluster, the b points of
    THIS site farthest from the global center ("find_border").  Move test
    for a single point x from cluster g to cluster j (treating {x} as a
    singleton merge, per the s(i,j) formula):
        gain_remove = N_g/(N_g-1) * d(c_g, x)^2
        cost_add    = N_j/(N_j+1) * d(c_j, x)^2
    Move iff cost_add < gain_remove (strict SSE decrease).
    """
    n, d = x.shape
    m = merged.stats.n_slots
    glabel = merged.labels[point_slot]  # (n,) global slot per point

    st = merged.stats
    alive = st.sizes > 0

    # --- border candidates: top-b farthest per global cluster, this site ---
    # Each point is scored against its OWN global centre only, and the
    # per-cluster top-b comes from one sort by (cluster, -distance,
    # index), so nothing (n, M)-sized is built: at 200 sites x 20
    # sub-clusters that matrix alone is n x 4000 floats per site.
    own_d2 = jnp.sum((x - st.centers[glabel]) ** 2, axis=-1)  # (n,)
    pos = jnp.arange(n, dtype=jnp.int32)
    order = jnp.lexsort((pos, -own_d2, glabel))  # cluster-major, farthest first
    slot_ids = jnp.arange(m, dtype=jnp.int32)
    start = jnp.searchsorted(glabel[order], slot_ids, side="left")  # (M,)
    count = jnp.searchsorted(glabel[order], slot_ids, side="right") - start
    rank = jnp.arange(min(b, n), dtype=jnp.int32)[None, :]  # (1, b)
    cand_valid = rank < count[:, None]  # (M, b)
    cand_idx = order[jnp.where(cand_valid, start[:, None] + rank, 0)]

    cand_flat = cand_idx.reshape(-1)  # (M*b,)
    valid_flat = cand_valid.reshape(-1)

    def move_one(carry, ci):
        sizes, centers, sse, glabel = carry
        idx, ok = ci
        xi = x[idx]
        g = glabel[idx]
        dg2 = jnp.sum((xi - centers[g]) ** 2)
        # closest OTHER live global cluster
        d2 = jnp.sum((xi[None, :] - centers) ** 2, axis=-1)
        d2 = jnp.where(alive & (sizes > 0), d2, jnp.inf)
        d2 = d2.at[g].set(jnp.inf)
        j = jnp.argmin(d2).astype(jnp.int32)
        dj2 = d2[j]
        ng, nj = sizes[g], sizes[j]
        gain_remove = jnp.where(ng > 1, ng / jnp.maximum(ng - 1.0, 1e-30) * dg2, 0.0)
        cost_add = nj / (nj + 1.0) * dj2
        do = ok & (ng > 1) & jnp.isfinite(dj2) & (cost_add < gain_remove)

        def apply(args):
            sizes, centers, sse, glabel = args
            cg_new = jnp.where(ng > 1, (sizes[g] * centers[g] - xi) / jnp.maximum(ng - 1.0, 1e-30), centers[g])
            cj_new = (sizes[j] * centers[j] + xi) / (nj + 1.0)
            sizes = sizes.at[g].add(-1.0).at[j].add(1.0)
            centers = centers.at[g].set(cg_new).at[j].set(cj_new)
            sse = sse.at[g].add(-gain_remove).at[j].add(cost_add)
            glabel = glabel.at[idx].set(j)
            return sizes, centers, sse, glabel

        carry = jax.lax.cond(do, apply, lambda a: a, (sizes, centers, sse, glabel))
        return carry, do

    carry0 = (st.sizes, st.centers, st.sse, glabel)
    (sizes, centers, sse, glabel), moved = jax.lax.scan(
        move_one, carry0, (cand_flat, valid_flat)
    )
    return glabel, SuffStats(sizes=sizes, centers=centers, sse=sse)


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


class VClusterResult(NamedTuple):
    labels: jax.Array  # (s, n) global slot label per point
    merged: MergeResult
    site_stats: SuffStats  # (s, k, ...) pre-merge sub-cluster stats
    comm_bytes: jax.Array  # () — bytes of statistics exchanged (the ONLY comm)


def _site_local(key, x, cfg: VClusterConfig):
    res = kmeans(key, x, cfg.k_local, iters=cfg.kmeans_iters, use_kernel=cfg.use_kernel)
    return res.assign, res.stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def _site_local_batch(keys, xs, cfg: VClusterConfig):
    """Fused fan-out: per-site K-Means for every site in ONE vmapped
    dispatch — what the batched execution backend calls instead of the
    per-site host loop."""
    return jax.vmap(lambda k, x: _site_local(k, x, cfg))(keys, xs)


@functools.partial(jax.jit, static_argnames=("b",))
def _perturb_batch(xs, slots, merged: MergeResult, b: int):
    """Fused fan-out: border perturbation for every site in ONE vmapped
    dispatch (site-local by construction — the merged stats are
    replicated, exactly as in the pooled driver)."""
    return jax.vmap(lambda x, s: perturb_site(x, s, merged, b)[0])(xs, slots)


@functools.partial(jax.jit, static_argnames=("b",))
def _perturb_batch_many(xs, slots, merged: MergeResult, b: int):
    """Like ``_perturb_batch`` but with one MergeResult PER MEMBER
    (leaves stacked on a leading axis) — the cross-request fused waves
    of the serving layer carry each request's own merge result."""
    return jax.vmap(lambda x, s, m: perturb_site(x, s, m, b)[0])(xs, slots, merged)


@functools.partial(jax.jit, static_argnames=("cfg",))
def vcluster_pooled(key: jax.Array, xs: jax.Array, cfg: VClusterConfig = VClusterConfig()) -> VClusterResult:
    """Reference driver: xs is (s, n, D) — s sites' datasets stacked.

    Semantically identical to the shard_map driver; the "gather" is free.
    """
    s, n, d = xs.shape
    keys = jax.random.split(key, s)
    assigns, per_site = jax.vmap(lambda k, x: _site_local(k, x, cfg))(keys, xs)
    merged = merge_gathered(per_site, cfg)

    k = cfg.k_local
    offsets = (jnp.arange(s, dtype=jnp.int32) * k)[:, None]
    point_slots = assigns + offsets  # (s, n) slot ids

    def site_perturb(x, slots):
        lbl, _ = perturb_site(x, slots, merged, cfg.border_candidates)
        return lbl

    labels = jax.vmap(site_perturb)(xs, point_slots)
    comm = jnp.asarray(s * k * (d + 2) * 4, jnp.int32)  # stats triples, f32
    return VClusterResult(labels=labels, merged=merged, site_stats=per_site, comm_bytes=comm)


def vcluster_shard_map(mesh, axis: str, cfg: VClusterConfig = VClusterConfig()):
    """Build the distributed driver: each shard along ``axis`` is one grid
    site.  The single communication is ``lax.all_gather`` of SuffStats
    (paper: "the only bookkeeping needed from the other sites is centers,
    sizes and variances").  Merge runs redundantly per site — identical
    output everywhere (logical merge).

    Returns fn(key (s,2) uint32 per-site keys, x_global (S*n, D)) ->
    (labels (S*n,), merged MergeResult replicated).
    """
    n_sites = mesh.shape[axis]
    k = cfg.k_local

    def body(keys, x):  # keys: (1, 2); x: (n, D) — this site's shard
        key = keys[0]
        assign, st = _site_local(key, x, cfg)
        gathered = jax.lax.all_gather(st, axis)  # (s, k, ...) tiny
        merged = merge_gathered(gathered, cfg)
        site_idx = jax.lax.axis_index(axis)
        slots = assign + site_idx.astype(jnp.int32) * k
        labels, _ = perturb_site(x, slots, merged, cfg.border_candidates)
        return labels, merged

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P()),  # merged result identical on every site
        check_vma=False,
    )
    return fn


# ---------------------------------------------------------------------------
# SiteJob decomposition (the grid-workflow view of Algorithm 1)
# ---------------------------------------------------------------------------


def vcluster_site_jobs(
    key: jax.Array,
    xs: jax.Array,
    cfg: VClusterConfig = VClusterConfig(),
    *,
    sync=None,
    measured: dict | None = None,
) -> list:
    """Decompose Algorithm 1 into ``workflow.sitejob.SiteJob``s.

    Stage 1: per-site K-Means sub-clustering (``cluster_i``; the Pallas
    assignment kernel when ``cfg.use_kernel``).  Stage 2: the single
    synchronization (``merge``) — ``sync(per_site_stats) -> MergeResult``
    is injected by the runtime (shard_map all_gather on a device mesh, or
    the default in-process pooled merge).  Stage 3: per-site border
    perturbation (``perturb_i`` — no inter-site communication; the final
    point labels are staged back to the submit node, ``output_bytes``).
    The terminal ``collect`` job's result is a ``VClusterResult``.

    All jobs return TimedResults, so the engine's grid clock is advanced by
    real measured kernel time; ``measured`` (if given) receives the same
    numbers for cross-checking the engine's ledger.

    The per-site fan-outs (``cluster_i``, ``perturb_i``) also carry
    ``batch_key``/``batched_fn`` hooks: under the ``batched`` execution
    backend the whole fan-out runs as ONE vmapped dispatch across the
    site axis, with the measured batch time apportioned per job.
    """
    from repro.workflow.sitejob import SiteJob, timed, timed_batch

    xs = jnp.asarray(xs)
    s, n, d = xs.shape
    k = cfg.k_local
    keys = jax.random.split(key, s)
    stats_nbytes = k * (d + 2) * 4  # (N, center, SSE) triples, f32
    if sync is None:
        sync = functools.partial(merge_gathered, cfg=cfg)
    jobs: list[SiteJob] = []

    def cluster_fn(i):
        def fn():
            return _site_local(keys[i], xs[i], cfg)

        return fn

    def cluster_batched(bargs, argss):
        # bargs carry (site, site_key): a cross-request merged wave
        # (service fusion) executes under the FIRST member's closure, and
        # each member's PRNG key is request-specific (per-request seeds)
        # while the site data is pinned identical by the fuse signature
        idx = jnp.asarray([i for i, _ in bargs], dtype=jnp.int32)
        bkeys = jnp.stack([kk for _, kk in bargs])
        assigns, st = _site_local_batch(bkeys, xs[idx], cfg)
        return [
            (assigns[j], SuffStats(sizes=st.sizes[j], centers=st.centers[j], sse=st.sse[j]))
            for j in range(len(bargs))
        ]

    for i in range(s):
        jobs.append(
            SiteJob(
                name=f"cluster_{i}",
                fn=timed(cluster_fn(i), measured, f"cluster_{i}"),
                site=i,  # GridModel.transfer_s normalizes to its link matrix
                input_bytes=int(xs[i].nbytes),
                output_bytes=stats_nbytes,
                batch_key="cluster",
                batched_fn=timed_batch(cluster_batched, measured),
                batch_arg=(i, keys[i]),
            )
        )

    def merge_fn(*site_out):
        per_site = SuffStats(
            sizes=jnp.stack([st.sizes for _, st in site_out]),
            centers=jnp.stack([st.centers for _, st in site_out]),
            sse=jnp.stack([st.sse for _, st in site_out]),
        )
        return sync(per_site)

    jobs.append(
        SiteJob(
            name="merge",
            fn=timed(merge_fn, measured, "merge"),
            deps=[f"cluster_{i}" for i in range(s)],
            input_bytes=s * stats_nbytes,  # the all_gather payload
        )
    )

    def perturb_fn(i):
        def fn(site_out, merged):
            assign, _ = site_out
            slots = assign + jnp.int32(i * k)
            labels, _ = perturb_site(xs[i], slots, merged, cfg.border_candidates)
            return labels

        return fn

    def perturb_batched(bargs, argss):
        idx = jnp.asarray(bargs, dtype=jnp.int32)
        assigns = jnp.stack([site_out[0] for site_out, _ in argss])
        slots = assigns + (idx * jnp.int32(k))[:, None]
        mergeds = [m for _, m in argss]
        if all(m is mergeds[0] for m in mergeds):
            # one engine run: every member shares the same "merge" dep —
            # keep the exact broadcast path (bitwise-stable, what the
            # cross-backend conformance suite pins)
            labels = _perturb_batch(xs[idx], slots, mergeds[0], cfg.border_candidates)
        else:
            # cross-request merged wave: one MergeResult per member
            merged = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *mergeds)
            labels = _perturb_batch_many(xs[idx], slots, merged, cfg.border_candidates)
        return [labels[j] for j in range(len(bargs))]

    for i in range(s):
        jobs.append(
            SiteJob(
                name=f"perturb_{i}",
                fn=timed(perturb_fn(i), measured, f"perturb_{i}"),
                deps=[f"cluster_{i}", "merge"],
                site=i,  # GridModel.transfer_s normalizes to its link matrix
                output_bytes=n * 4,  # int32 point labels staged back
                batch_key="perturb",
                batched_fn=timed_batch(perturb_batched, measured),
                batch_arg=i,
            )
        )

    def collect_fn(merged, *rest):
        labels = jnp.stack(list(rest[:s]))
        site_out = rest[s:]
        per_site = SuffStats(
            sizes=jnp.stack([st.sizes for _, st in site_out]),
            centers=jnp.stack([st.centers for _, st in site_out]),
            sse=jnp.stack([st.sse for _, st in site_out]),
        )
        comm = jnp.asarray(s * stats_nbytes, jnp.int32)
        return VClusterResult(labels=labels, merged=merged, site_stats=per_site, comm_bytes=comm)

    jobs.append(
        SiteJob(
            name="collect",
            fn=timed(collect_fn, measured, "collect"),
            deps=["merge", *[f"perturb_{i}" for i in range(s)], *[f"cluster_{i}" for i in range(s)]],
        )
    )
    return jobs
