#!/usr/bin/env python3
"""Drive the served mining path once on a TPU, at real data sizes, and
check what comes out.

    python3 chip_smoke.py               # one chip: device, kernels, service
    python3 chip_smoke.py --chips 4     # four chips: shard_map vs pooled
                                        # vclustering, and nothing else

One process does everything (a chip belongs to one process at a time).
The phases, in order:

  * device  — JAX must find a TPU; there is no CPU branch.
  * kernels — ``ops.support_count``, ``ops.support_count_prune``,
    ``ops.kmeans_assign`` and their fused ``*_sites`` forms at the
    service's per-site shapes; every compiled program must hold a
    ``tpu_custom_call`` (Mosaic compiled it, nothing was interpreted), and
    every result must match ``kernels/ref.py``.
  * service — a two-tenant ``MiningService`` on the ``batched`` backend
    with the kernels on, over 1e6 transactions x 96 items and 5e6 points
    x 8 dims split across the paper's 200 sites; checks every request's
    status, the ledger (no failures, a fused dispatch, no fused->serial
    fallback, a cache hit) and the results against independent paths.

Earlier lines give each phase's set-up, compile and run wall times.  The
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every check passed; any failure exits non-zero first.
The phase functions take their sizes, so a CPU test can run them small
(with Pallas in interpret mode there, ``require_mosaic=False``).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Paper scale (arXiv:1703.09807 §5.2): 200 sites, k = 4 itemsets, 20
# sub-clusters per site, 4e6 transactions, 5e7 points.  The paper fixes
# neither the item universe nor the point dimension; the widths below
# follow benchmarks/bench_gfm_vs_fdm.py and benchmarks/bench_clustering.py.
N_SITES = 200
N_TX = 1_000_000
N_ITEMS = 96
N_PTS = 5_000_000
DIM = 8
N_COMPONENTS = 12
K_ITEMSETS = 4
MINSUP = 0.05
K_LOCAL = 20
N_CAND = 1024  # candidate masks per site in the kernel phase
CUTS = (
    "transactions 4e6 -> 1e6: data.synthetic.ibm_transactions loops in Python "
    "per transaction (~120 us each on a TPU v5e host), about 8 minutes of set-up at 4e6",
    "points 5e7 -> 5e6: an (N, 8) f32 array tiles to 128 lanes on the TPU, "
    "25.6 GB at 5e7 points against 16 GB of HBM",
)
FOUR_CHIP_SITES = 4


class SmokeError(RuntimeError):
    """A check of the smoke failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


@contextmanager
def phase(name: str, clock, times: dict):
    """Time one phase; ``clock`` is ``repro.obs.compiles`` (or None), so
    the phase reports the seconds of its XLA backend compiles apart from
    its wall time."""
    c0 = clock() if clock is not None else None
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = (clock() - c0).seconds if clock is not None else float("nan")
    times[name] = {"wall_s": wall, "compile_s": comp}
    log(f"phase {name}: wall {wall:.3f} s, of which compile {comp:.3f} s")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def device_phase(min_count: int = 1) -> dict:
    """The device JAX runs on; raises unless it is a TPU with at least
    ``min_count`` chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SmokeError(
            f"needs a TPU, but JAX found platform {d.platform!r} "
            f"({d.device_kind}, {len(devs)} device(s))"
        )
    check(len(devs) >= min_count, f"needs {min_count} TPU chips, found {len(devs)}")
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _random_tx(rng, rows: int, n_items: int):
    """Packed (rows, W) uint32 transactions, each item present w.p. 1/8."""
    import numpy as np

    from repro.core.apriori import pack_bool_matrix

    return pack_bool_matrix(rng.integers(0, 8, size=(rows, n_items), dtype=np.uint8) == 0)


def _random_masks(rng, rows: int, n_items: int):
    """Packed (rows, W) uint32 candidate masks of 2-4 distinct items; row
    0 is the empty itemset (the pad-row correction's edge case)."""
    import numpy as np

    from repro.core.apriori import pack_bool_matrix

    dense = np.zeros((rows, n_items), dtype=bool)
    size = rng.integers(2, 5, size=rows)
    for j in range(4):
        pick = rng.integers(0, n_items, size=rows)
        dense[np.arange(rows)[size > j], pick[size > j]] = True
    dense[0] = False
    return pack_bool_matrix(dense)


def _compile(fn, args, require_mosaic: bool):
    """jit + lower + compile ``fn``; returns (compiled, seconds).  With
    ``require_mosaic`` the program must hold a Mosaic kernel."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    if require_mosaic:
        check(
            "tpu_custom_call" in compiled.as_text(),
            f"{getattr(fn, '__name__', fn)}: compiled program holds no tpu_custom_call "
            "(the Pallas kernel was not compiled by Mosaic)",
        )
    return compiled, secs


def _run(compiled, args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def _check_assign(x, c, a_got, d_got, a_ref, d_ref, what: str) -> int:
    """Assignments must match exactly except where the two picked centres
    are tied within f32 rounding of the |x|^2 + |c|^2 - 2 x.c form; the
    distances must be allclose at that rounding.  Returns the number of
    tied points that differ."""
    import numpy as np

    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    a_got, a_ref = np.asarray(a_got), np.asarray(a_ref)
    scale = (x * x).sum(-1).max() + (c * c).sum(-1).max()
    tol = 64 * np.finfo(np.float32).eps * scale
    check(
        np.allclose(np.asarray(d_got), np.asarray(d_ref), rtol=1e-5, atol=tol),
        f"{what}: min_d2 differs from the reference beyond {tol:.3g}",
    )
    bad = np.nonzero(a_got != a_ref)[0]
    if bad.size:
        xb = x[bad]
        gap = np.abs(((xb - c[a_got[bad]]) ** 2).sum(-1) - ((xb - c[a_ref[bad]]) ** 2).sum(-1))
        check(
            bool((gap <= tol).all()),
            f"{what}: {int((gap > tol).sum())} of {x.shape[0]} assignments differ "
            f"from the reference without a tie (largest gap {gap.max():.3g})",
        )
    return int(bad.size)


def _rel_gap(d2, i, j):
    """|d2[:, i] - d2[:, j]| / (d2[:, i] + d2[:, j]) per row."""
    import numpy as np

    r = np.arange(d2.shape[0])
    a, b = d2[r, i], d2[r, j]
    return np.abs(a - b) / np.maximum(a + b, np.finfo(np.float32).tiny)


def _check_clustering(x, a_got, c_got, a_ref, c_ref, what: str) -> tuple[int, float]:
    """Two k-means runs over the same points agree up to f32 rounding.

    The kernel and the jnp path round distances differently on the chip,
    so a few boundary points flip and the runs' centres then drift apart
    by rounding.  A k-means result's centres are the means of its final
    assignment, one Lloyd step past the centres that assignment was made
    against, so the yardstick is that step: under each run's centres, every
    point assigned differently must sit closer to the boundary (relative
    gap of its two squared distances) than twice the largest gap among the
    points the reference run's own next step would move, or than f32
    rounding where it has converged.  The bound comes from the reference
    alone, so wrong assignments in ``a_got`` cannot widen it; they have a
    gap of order one.  Returns (points that differ, the bound)."""
    import numpy as np

    x = np.asarray(x, np.float64)
    a_got, a_ref = np.asarray(a_got), np.asarray(a_ref)
    bad = np.nonzero(a_got != a_ref)[0]

    def d2_to(c, rows):  # (len(rows), k) squared distances
        return np.stack([((x[rows] - cj) ** 2).sum(-1) for cj in np.asarray(c, np.float64)], 1)

    d2 = d2_to(c_ref, slice(None))
    moved = np.nonzero(d2.argmin(1) != a_ref)[0]
    tol = 64 * np.finfo(np.float32).eps
    if moved.size:
        tol = max(tol, 2 * float(_rel_gap(d2[moved], a_ref[moved], d2[moved].argmin(1)).max()))
    for c in (c_ref, c_got) if bad.size else ():
        gap = _rel_gap(d2_to(c, bad), a_got[bad], a_ref[bad])
        check(
            bool((gap <= tol).all()),
            f"{what}: {int((gap > tol).sum())} of {x.shape[0]} assignments differ "
            f"without a near-tie (largest relative gap {gap.max():.3g}, bound {tol:.3g})",
        )
    return int(bad.size), tol


def kernel_phase(
    *,
    n_sites: int,
    n_tx: int,
    n_items: int,
    n_cand: int,
    n_pts: int,
    dim: int,
    k: int,
    seed: int,
    require_mosaic: bool = True,
) -> dict:
    """Compile, run and check every mining kernel entry point at one
    site's shapes (single forms: site 0) and across ``n_sites`` (fused
    ``*_sites`` forms).  Returns per-kernel compile/run seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import gaussian_mixture
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    tx_s = _random_tx(rng, n_sites * n_tx, n_items).reshape(n_sites, n_tx, -1)
    mk_s = _random_masks(rng, n_sites * n_cand, n_items).reshape(n_sites, n_cand, -1)
    mc_s = rng.integers(1, max(2, n_tx // 16), size=n_sites).astype(np.int32)
    pts, _ = gaussian_mixture(seed, n_sites * n_pts, dim, N_COMPONENTS)
    xs = pts.reshape(n_sites, n_pts, dim)
    cs = xs[:, :k] + np.float32(0.5)  # k centres per site, off the points
    tx_s, mk_s, mc_s, xs_d, cs_d = map(jnp.asarray, (tx_s, mk_s, mc_s, xs, cs))

    # references, one site at a time so the (N, C, W) hit tensor stays small
    ref_count = jax.jit(lambda t, m: jax.lax.map(lambda a: ref.support_count_ref(*a), (t, m)))
    ref_assign = jax.jit(lambda x, c: jax.lax.map(lambda a: ref.kmeans_assign_ref(*a), (x, c)))
    want_counts = np.asarray(ref_count(tx_s, mk_s))
    want_a, want_d = (np.asarray(v) for v in ref_assign(xs_d, cs_d))
    want_freq = want_counts >= np.asarray(mc_s)[:, None]

    cases = [
        ("support_count", ops.support_count, (tx_s[0], mk_s[0])),
        ("support_count_prune", ops.support_count_prune, (tx_s[0], mk_s[0], mc_s[0])),
        ("kmeans_assign", ops.kmeans_assign, (xs_d[0], cs_d[0])),
        ("support_count_sites", ops.support_count_sites, (tx_s, mk_s)),
        ("support_count_prune_sites", ops.support_count_prune_sites, (tx_s, mk_s, mc_s)),
        ("kmeans_assign_sites", ops.kmeans_assign_sites, (xs_d, cs_d)),
    ]
    out: dict = {}
    for name, fn, args in cases:
        compiled, t_comp = _compile(fn, args, require_mosaic)
        got, t_run = _run(compiled, args)
        sites = name.endswith("_sites")
        sl = slice(None) if sites else 0
        ties = 0
        if name.startswith("support_count_prune"):
            cnt, freq = (np.asarray(v) for v in got)
            check(np.array_equal(cnt, want_counts[sl]), f"{name}: counts differ from ref")
            check(np.array_equal(freq, want_freq[sl]), f"{name}: frequent mask differs")
        elif name.startswith("support_count"):
            check(np.array_equal(np.asarray(got), want_counts[sl]), f"{name}: counts differ")
        else:
            a, d = (np.asarray(v) for v in got)
            x_all = xs.reshape(-1, dim) if sites else xs[0]
            if sites:
                # one flat point axis; per-site centre ids offset into one table
                off = (np.arange(n_sites) * k)[:, None]
                ties = _check_assign(
                    x_all, cs.reshape(-1, dim), (a + off).ravel(), d.ravel(),
                    (want_a + off).ravel(), want_d.ravel(), name,
                )
            else:
                ties = _check_assign(x_all, cs[0], a, d, want_a[0], want_d[0], name)
        out[name] = {"compile_s": t_comp, "run_s": t_run, "tied_assignments": ties}
        log(f"kernel {name}: compile {t_comp:.3f} s, run {t_run:.6f} s, matches ref"
            + (f" ({ties} tied assignments differ)" if ties else ""))
    return out


# ---------------------------------------------------------------------------
# service
# ---------------------------------------------------------------------------


def _frequent(result) -> dict:
    return {tuple(k): int(v) for k, v in result.frequent.items()}


def _points_requests(svc, pts, *, k_local: int, n_components: int) -> dict:
    """The points-side first wave, shared by the kernel service and its
    ``use_kernel=False`` reference: load the points, then submit
    vclustering and a cold kmeans.  Returns request ids."""
    svc.append_points("pts", pts)
    return {
        "vclustering": svc.submit("alice", "vclustering", "pts", {"k_local": k_local}),
        "kmeans": svc.submit("bob", "kmeans", "pts", {"k": n_components}),
    }


def service_phase(
    *,
    n_sites: int,
    n_tx: int,
    n_items: int,
    n_pts: int,
    dim: int,
    k: int,
    minsup: float,
    k_local: int,
    n_components: int,
    seed: int,
    clock=None,
) -> dict:
    """The two-tenant service run and every check on it.  Returns the
    ledger summary and per-step wall/compile seconds."""
    import jax
    import numpy as np

    from repro.core.apriori import TransactionDB, local_apriori
    from repro.data.synthetic import gaussian_mixture, ibm_transactions
    from repro.launch.serve import MiningService

    times: dict = {}
    with phase("service/data", clock, times):
        n_tx_new, n_pts_new = max(n_sites, n_tx // 100), max(n_sites, n_pts // 100)
        dense = ibm_transactions(seed, n_tx, n_items, avg_tx_len=10, n_patterns=24)
        dense_new = ibm_transactions(seed + 1, n_tx_new, n_items, avg_tx_len=10, n_patterns=24)
        pts, _ = gaussian_mixture(seed, n_pts, dim, n_components)
        pts_new, _ = gaussian_mixture(seed + 1, n_pts_new, dim, n_components)
    log(f"service data: {n_tx} + {n_tx_new} transactions x {n_items} items, "
        f"{n_pts} + {n_pts_new} points x {dim} dims, {n_sites} sites")

    with phase("service/build", clock, times):
        svc = MiningService(
            backend="batched", n_sites=n_sites, count_backend="kernel", use_kernel=True
        )
        svc.register_dataset("tx", "transactions", n_items=n_items)
        svc.register_dataset("pts", "points", dim=dim)
        svc.append_transactions("tx", dense)
        mine = {"k": k, "minsup": minsup}
        sibling = {"k": k, "minsup": round(minsup * 1.2, 6)}
        ids = {
            "gfm": svc.submit("alice", "gfm", "tx", mine),
            "fdm": svc.submit("bob", "fdm", "tx", mine),
            "cd_apriori": svc.submit("alice", "cd_apriori", "tx", mine),
            # same exec_batch_key as "fdm": the pair runs as one fused dispatch
            "fdm_sibling": svc.submit("bob", "fdm", "tx", sibling),
            "apriori": svc.submit("alice", "apriori", "tx", mine),
            "topk": svc.submit("bob", "topk", "tx", {"k": k, "top": 10}),
        }
        ids.update(_points_requests(svc, pts, k_local=k_local, n_components=n_components))

    with phase("service/wave1", clock, times):
        svc.drain(max_requests=len(ids))
    with phase("service/repeat", clock, times):
        ids["gfm_repeat"] = svc.submit("bob", "gfm", "tx", mine)
        svc.drain()
    with phase("service/append+requery", clock, times):
        svc.append_transactions("tx", dense_new)
        svc.append_points("pts", pts_new)
        ids["apriori_v2"] = svc.submit("alice", "apriori", "tx", mine)
        ids["kmeans_v2"] = svc.submit("bob", "kmeans", "pts", {"k": n_components})
        svc.drain()

    led = svc.ledger()
    status = {name: svc.poll(rid) for name, rid in ids.items()}
    log(f"service ledger: requests={len(led['requests'])} status={status} "
        f"executions={led['executions']} exec_groups={led['exec_groups']} "
        f"device_dispatches={led['device_dispatches']} fused_requests={led['fused_requests']} "
        f"fused_fallbacks={led['fused_fallbacks']} failures={led['failures']} "
        f"cache_hits={led['cache']['hits']}")
    for name, rid in ids.items():
        r = svc.request(rid)
        log(f"request {name}: {r.status}, service {r.service_s:.3f} s, compute "
            f"{r.compute_s:.3f} s, fused={r.fused}, cache_hit={r.cache_hit}")
    errors = {n: svc.request(r).error for n, r in ids.items() if svc.request(r).error}
    check(all(s == "done" for s in status.values()), f"requests not done: {status} {errors}")
    check(led["failures"] == 0, f"ledger failures={led['failures']}: {errors}")
    check(led["fused_requests"] >= 1, "no request was served by a fused dispatch")
    check(
        led["fused_fallbacks"] == 0,
        f"fused dispatch fell back to serial: {led['fused_fallback_errors']}",
    )
    check(svc.request(ids["gfm_repeat"]).cache_hit, "the repeated gfm query missed the cache")
    res = {name: svc.result(rid) for name, rid in ids.items()}

    with phase("service/reference", clock, times):
        # the three grid miners are exact: identical frequent itemsets
        gfm, fdm, cd = (_frequent(res[a]) for a in ("gfm", "fdm", "cd_apriori"))
        check(gfm == fdm, f"gfm and fdm disagree ({len(gfm)} vs {len(fdm)} itemsets)")
        check(gfm == cd, f"gfm and cd_apriori disagree ({len(gfm)} vs {len(cd)} itemsets)")
        check(_frequent(res["gfm_repeat"]) == gfm, "cached gfm result differs")
        # the fused sibling's higher threshold keeps exactly the itemsets
        # whose exact global count reaches it
        g_min = math.ceil(sibling["minsup"] * n_tx)
        check(
            _frequent(res["fdm_sibling"]) == {i: c for i, c in fdm.items() if c >= g_min},
            "fused fdm sibling differs from the thresholded fdm result",
        )
        # incremental apriori after the append == from-scratch jnp Apriori
        full = TransactionDB.from_dense(np.concatenate([dense, dense_new]))
        mc = max(1, math.ceil(minsup * full.n_tx))
        want = local_apriori(full, k, mc, backend="jnp")
        got = res["apriori_v2"]
        check(got.frequent == want.frequent, "apriori after append differs from local_apriori")
        check(
            all(got.counts[i] == want.counts[i] for lv in want.frequent for i in want.frequent[lv]),
            "apriori after append: counts differ from local_apriori",
        )
        # the clustering path with the kernel off, same data and order
        ref_svc = MiningService(backend="batched", n_sites=n_sites, use_kernel=False)
        ref_svc.register_dataset("pts", "points", dim=dim)
        ref_ids = _points_requests(ref_svc, pts, k_local=k_local, n_components=n_components)
        ref_svc.drain()
        ref_svc.append_points("pts", pts_new)
        ref_ids["kmeans_v2"] = ref_svc.submit("bob", "kmeans", "pts", {"k": n_components})
        ref_svc.drain()
        for name, rid in ref_ids.items():
            check(ref_svc.poll(rid) == "done", f"reference {name}: {ref_svc.request(rid).error}")
        ref_res = {name: ref_svc.result(rid) for name, rid in ref_ids.items()}
        vk, vr = res["vclustering"], ref_res["vclustering"]
        n_diff = int(np.sum(np.asarray(vk.labels) != np.asarray(vr.labels)))
        check(n_diff == 0, f"vclustering labels: {n_diff} differ from use_kernel=False")
        check(
            int(vk.merged.n_global) == int(vr.merged.n_global)
            and int(vk.merged.n_merges) == int(vr.merged.n_merges),
            "vclustering merge differs from use_kernel=False",
        )
        x_all = {"kmeans": pts, "kmeans_v2": np.concatenate([pts, pts_new])}
        for name in ("kmeans", "kmeans_v2"):
            got, want = res[name], ref_res[name]
            ties, tol = _check_clustering(x_all[name], got.assign, got.centers, want.assign,
                                          want.centers, f"{name} vs use_kernel=False")
            check(
                np.allclose(float(got.inertia), float(want.inertia), rtol=1e-5),
                f"{name} inertia differs from use_kernel=False",
            )
            log(f"{name}: equals use_kernel=False ({ties} near-tied assignments differ, "
                f"relative gap bound {tol:.3g})")
        jax.block_until_ready(vk.labels)
    log(f"service checks passed: {len(gfm)} frequent itemsets (k<={k}, minsup={minsup}), "
        f"{int(vk.merged.n_global)} global clusters from {n_sites}x{k_local} sub-clusters")
    return {"ledger": {k_: led[k_] for k_ in (
        "executions", "exec_groups", "device_dispatches", "fused_requests",
        "fused_fallbacks", "failures")}, "times": times}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(*, n_pts: int, dim: int, k_local: int, seed: int) -> dict:
    """vclustering through ``GridRuntime.for_sites(4, sync="shard_map")``
    against the same run with ``sync="pooled"``: labels and the merge
    must be identical.  Reports where the per-site compute ran."""
    import jax
    import numpy as np

    from repro.core.vclustering import VClusterConfig
    from repro.data.synthetic import gaussian_mixture, split_sites
    from repro.runtime.gridruntime import GridRuntime

    pts, _ = gaussian_mixture(seed, n_pts, dim, N_COMPONENTS)
    xs = split_sites(pts, FOUR_CHIP_SITES, seed=0)
    cfg = VClusterConfig(k_local=k_local, kmeans_iters=15, use_kernel=True)
    key = jax.random.PRNGKey(seed)
    runs = {}
    for sync in ("shard_map", "pooled"):
        rt = GridRuntime.for_sites(FOUR_CHIP_SITES, sync=sync)
        t0 = time.perf_counter()
        run = rt.run_vclustering(key, xs, cfg)
        jax.block_until_ready(run.result.labels)
        check(run.sync_mode == sync, f"asked for sync={sync}, ran {run.sync_mode}")
        runs[sync] = run
        r = run.result
        log(f"4 chips, sync={sync}: wall {time.perf_counter() - t0:.3f} s; per-site "
            f"stats on {sorted(str(d) for d in r.site_stats.centers.devices())}, "
            f"merge on {sorted(str(d) for d in r.merged.labels.devices())}, "
            f"labels on {sorted(str(d) for d in r.labels.devices())}")
    a, b = runs["shard_map"].result, runs["pooled"].result
    check(np.array_equal(np.asarray(a.labels), np.asarray(b.labels)),
          "shard_map labels differ from pooled")
    for field in ("labels", "n_merges", "n_global"):
        check(np.array_equal(np.asarray(getattr(a.merged, field)),
                             np.asarray(getattr(b.merged, field))),
              f"shard_map merge.{field} differs from pooled")
    for field in ("sizes", "centers", "sse"):
        check(np.array_equal(np.asarray(getattr(a.merged.stats, field)),
                             np.asarray(getattr(b.merged.stats, field))),
              f"shard_map merged stats.{field} differ from pooled")
    log("4 chips: shard_map labels and merge equal pooled")
    return {"site_devices": sorted(str(d) for d in a.site_stats.centers.devices())}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of every generated input")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map-vs-pooled vclustering comparison")
    args = ap.parse_args(argv)

    import repro
    from repro.launch.mesh import enable_compile_cache

    if Path(repro.__file__).resolve().parents[1] != ROOT / "src":
        print(f"[smoke] FAILED: imported repro from {repro.__file__}, not {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    try:
        dev = device_phase(min_count=args.chips)
    except SmokeError as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 2
    log(f"compile cache: {cache}")
    from repro.obs import compiles as clock
    times: dict = {}
    try:
        if args.chips == 4:
            with phase("four_chips", clock, times):
                four_chip_phase(n_pts=N_PTS, dim=DIM, k_local=K_LOCAL, seed=args.seed)
            dev = {**dev, "count": FOUR_CHIP_SITES}
        else:
            log(f"sizes: {N_SITES} sites; {N_TX} transactions x {N_ITEMS} items "
                f"(k={K_ITEMSETS}, minsup={MINSUP}); {N_PTS} points x {DIM} dims, "
                f"{N_COMPONENTS} components, k_local={K_LOCAL}; the widths "
                f"{N_ITEMS} items and {DIM} dims are assumed (the paper fixes neither)")
            for cut in CUTS:
                log(f"cut from paper scale: {cut}")
            with phase("kernels", clock, times):
                kernel_phase(
                    n_sites=N_SITES, n_tx=N_TX // N_SITES, n_items=N_ITEMS, n_cand=N_CAND,
                    n_pts=N_PTS // N_SITES, dim=DIM, k=K_LOCAL, seed=args.seed,
                )
            with phase("service", clock, times):
                service_phase(
                    n_sites=N_SITES, n_tx=N_TX, n_items=N_ITEMS, n_pts=N_PTS, dim=DIM,
                    k=K_ITEMSETS, minsup=MINSUP, k_local=K_LOCAL,
                    n_components=N_COMPONENTS, seed=args.seed, clock=clock,
                )
    except Exception as e:  # noqa: BLE001 — any failed phase fails the smoke
        import traceback

        traceback.print_exc()
        print(f"[smoke] FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
