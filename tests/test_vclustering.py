"""Algorithm 1 (variance-based distributed clustering) behaviour tests."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kmeans import gap_statistic, kmeans
from repro.core.stats import SuffStats, stats_from_assignment
from repro.core.vclustering import (
    VClusterConfig,
    _perturb_batch,
    _perturb_batch_many,
    _site_local_batch,
    border_order,
    first_argmin,
    merge_gathered,
    perturb_site,
    vcluster_pooled,
)
from repro.data.synthetic import gaussian_mixture, split_sites


def planted(seed=0, n_comp=4, n=2000, d=2, spread=12.0, sigma=0.5):
    pts, lab = gaussian_mixture(seed, n, d, n_comp, spread=spread, sigma=sigma)
    return pts, lab


class TestKMeans:
    def test_recovers_separated_clusters(self):
        pts, _ = planted(seed=3)
        res = kmeans(jax.random.PRNGKey(0), jnp.asarray(pts), 4, iters=25)
        # every true component maps to exactly one center
        assert float(res.inertia) < 2 * pts.shape[0] * 0.5**2 * 2

    def test_fixed_iters_deterministic(self):
        pts, _ = planted(seed=4)
        r1 = kmeans(jax.random.PRNGKey(1), jnp.asarray(pts), 5)
        r2 = kmeans(jax.random.PRNGKey(1), jnp.asarray(pts), 5)
        assert np.array_equal(np.asarray(r1.assign), np.asarray(r2.assign))

    def test_gap_statistic_finds_k(self):
        pts, _ = planted(seed=5, n=600)
        k_hat, _ = gap_statistic(jax.random.PRNGKey(0), jnp.asarray(pts), 6, n_ref=2, iters=10)
        assert k_hat == 4


class TestDistributedClustering:
    def test_recovers_planted_structure_across_sites(self):
        pts, _ = planted(seed=0, n=2000)
        xs = split_sites(pts, 4, seed=1)
        cfg = VClusterConfig(k_local=8, kmeans_iters=20, border_candidates=4)
        res = vcluster_pooled(jax.random.PRNGKey(0), jnp.asarray(xs), cfg)
        assert int(res.merged.n_global) == 4
        # purity: points near each true center share one global label
        labels = np.asarray(res.labels).reshape(-1)
        flat = xs.reshape(-1, 2)
        rng_centers = np.random.default_rng(0).uniform(-12, 12, (4, 2))
        for c in rng_centers:
            near = np.linalg.norm(flat - c, axis=1) < 2.5
            if near.sum() < 10:
                continue
            near_labels = labels[near]
            purity = (near_labels == np.bincount(near_labels).argmax()).mean()
            assert purity > 0.95, (c, purity)

    def test_comm_is_stats_only(self):
        """The ONLY communication is s*k stat triples — KB not MB."""
        pts, _ = planted(seed=0, n=20_000, d=8)
        xs = split_sites(pts, 4, seed=1)
        cfg = VClusterConfig(k_local=10, kmeans_iters=10)
        res = vcluster_pooled(jax.random.PRNGKey(0), jnp.asarray(xs), cfg)
        data_bytes = xs.size * 4
        assert int(res.comm_bytes) < data_bytes / 100, "stats must be ≪ data"
        # and the ratio improves with n: comm is O(s*k*d), data O(n*d)

    def test_merge_is_deterministic_logical_labeling(self):
        """Any site computing the merge gets identical labels (paper's
        'logical merging at any site')."""
        pts, _ = planted(seed=7, n=1000)
        xs = split_sites(pts, 4, seed=2)
        cfg = VClusterConfig(k_local=6, kmeans_iters=15)
        r1 = vcluster_pooled(jax.random.PRNGKey(3), jnp.asarray(xs), cfg)
        r2 = vcluster_pooled(jax.random.PRNGKey(3), jnp.asarray(xs), cfg)
        assert np.array_equal(np.asarray(r1.merged.labels), np.asarray(r2.merged.labels))

    def test_perturbation_does_not_increase_sse(self):
        pts, _ = planted(seed=8, n=1000, sigma=1.2, spread=6.0)
        xs = split_sites(pts, 2, seed=0)
        cfg0 = VClusterConfig(k_local=8, kmeans_iters=15, border_candidates=0)
        cfg1 = cfg0._replace(border_candidates=8)
        # run with and without perturbation; global SSE (recomputed from
        # final labels) must not be worse with perturbation
        def sse_of(res, xs):
            labels = np.asarray(res.labels).reshape(-1)
            flat = np.asarray(xs).reshape(-1, xs.shape[-1])
            tot = 0.0
            for lbl in np.unique(labels):
                pts_l = flat[labels == lbl]
                tot += ((pts_l - pts_l.mean(0)) ** 2).sum()
            return tot

        r0 = vcluster_pooled(jax.random.PRNGKey(0), jnp.asarray(xs), cfg0)
        r1 = vcluster_pooled(jax.random.PRNGKey(0), jnp.asarray(xs), cfg1)
        assert sse_of(r1, xs) <= sse_of(r0, xs) * 1.001


SHARD_MAP_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "SRC")
import jax, jax.numpy as jnp, numpy as np
from repro.core.vclustering import VClusterConfig, vcluster_pooled, vcluster_shard_map
from repro.data.synthetic import gaussian_mixture, split_sites

pts, _ = gaussian_mixture(0, 2000, 2, 4, spread=12.0, sigma=0.5)
xs = split_sites(pts, 4, seed=1)
cfg = VClusterConfig(k_local=6, kmeans_iters=15, border_candidates=4)
key = jax.random.PRNGKey(0)
ref = vcluster_pooled(key, jnp.asarray(xs), cfg)

mesh = jax.make_mesh((4,), ("sites",))
fn = vcluster_shard_map(mesh, "sites", cfg)
keys = jax.random.split(key, 4)
labels, merged = fn(keys, jnp.asarray(xs.reshape(-1, 2)))
# the distributed path must produce the identical global structure
assert int(merged.n_global) == int(ref.merged.n_global), (merged.n_global, ref.merged.n_global)
assert np.array_equal(np.asarray(merged.labels), np.asarray(ref.merged.labels))
assert np.array_equal(np.asarray(labels).reshape(-1), np.asarray(ref.labels).reshape(-1))
print("SHARD_MAP_EQUIV_OK")
"""


class TestShardMapDriver:
    def test_shard_map_equals_pooled_reference(self, tmp_path):
        """The mesh-distributed driver (all_gather of stats + redundant
        logical merge) is bit-identical to the pooled oracle.  Runs in a
        subprocess with 4 host devices."""
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        script = SHARD_MAP_EQUIV.replace("SRC", os.path.abspath(src))
        p = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert "SHARD_MAP_EQUIV_OK" in p.stdout, p.stdout + p.stderr


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_border_order_is_the_three_key_lexsort(seed):
    # few distinct distances and clusters, so ties are the rule; zero,
    # tiny and infinite distances too
    rng = np.random.default_rng(seed)
    own_d2 = rng.integers(0, 4, 500).astype(np.float32) * np.float32(0.5)
    own_d2[rng.integers(0, 500, 20)] = rng.choice([1e-40, 3e38, np.inf], 20).astype(np.float32)
    own_d2 = jnp.asarray(own_d2)
    glabel = jnp.asarray(rng.integers(0, 6, 500).astype(np.int32))
    want = jnp.lexsort((jnp.arange(500, dtype=jnp.int32), -own_d2, glabel))
    assert np.array_equal(np.asarray(border_order(own_d2, glabel)), np.asarray(want))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_first_argmin_is_the_flat_argmin_unravelled(seed):
    # few distinct values, an inf or two and a nan: ties are the rule
    rng = np.random.default_rng(seed)
    sc = rng.integers(0, 3, (40, 30)).astype(np.float32)
    sc[rng.integers(0, 40, 3), rng.integers(0, 30, 3)] = np.inf
    if seed == 2:
        sc[7, 11] = np.nan
    for m in (sc, np.full_like(sc, np.inf)):
        want = jnp.unravel_index(jnp.argmin(jnp.asarray(m)), m.shape)
        assert tuple(map(int, first_argmin(jnp.asarray(m)))) == tuple(map(int, want))


@pytest.mark.parametrize("seed", [0, 1])
def test_perturb_site_counts_its_steps_live_candidates_and_moves(seed):
    # overlapping clusters, so that border points move
    pts, _ = planted(seed=seed, n=1200, spread=3.0, sigma=1.0)
    xs = jnp.asarray(split_sites(pts, 3, seed=seed))
    cfg = VClusterConfig(k_local=6, kmeans_iters=10, border_candidates=4)
    assigns, stats = _site_local_batch(jax.random.split(jax.random.PRNGKey(seed), 3), xs, cfg)
    merged = merge_gathered(stats, cfg)
    roots, m = np.asarray(merged.labels), merged.stats.n_slots
    moved_in_all = 0
    for i in range(3):
        slots = assigns[i] + i * cfg.k_local
        labels, _, counts = perturb_site(xs[i], slots, merged, cfg.border_candidates)
        before = roots[np.asarray(slots)]
        sizes = np.bincount(before, minlength=m)
        assert int(counts.steps) == m * cfg.border_candidates
        assert int(counts.live) == int(np.minimum(sizes, cfg.border_candidates).sum())
        assert int(counts.trips) == int(counts.live)
        # a border point moves once at most, so the moves are the changed labels
        assert int(counts.moved) == int((np.asarray(labels) != before).sum())
        moved_in_all += int(counts.moved)
    assert moved_in_all > 0


def full_scan_perturb(x, point_slot, merged, b):
    """``perturb_site`` as it was before its loop ran over the live
    candidates only: a ``lax.scan`` over all M*b candidate slots, the
    empty ones included.  The oracle of the live loop."""
    n, d = x.shape
    m = merged.stats.n_slots
    glabel = merged.labels[point_slot]
    st = merged.stats
    alive = st.sizes > 0
    own_d2 = jnp.sum((x - st.centers[glabel]) ** 2, axis=-1)
    order = border_order(own_d2, glabel)
    slot_ids = jnp.arange(m, dtype=jnp.int32)
    start = jnp.searchsorted(glabel[order], slot_ids, side="left")
    count = jnp.searchsorted(glabel[order], slot_ids, side="right") - start
    rank = jnp.arange(min(b, n), dtype=jnp.int32)[None, :]
    cand_valid = rank < count[:, None]
    cand_idx = order[jnp.where(cand_valid, start[:, None] + rank, 0)]
    cand_flat = cand_idx.reshape(-1)
    valid_flat = cand_valid.reshape(-1)

    def move_one(carry, ci):
        sizes, centers, sse, glabel = carry
        idx, ok = ci
        xi = x[idx]
        g = glabel[idx]
        dg2 = jnp.sum((xi - centers[g]) ** 2)
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
    (sizes, centers, sse, glabel), moved = jax.lax.scan(move_one, carry0, (cand_flat, valid_flat))
    counts = (jnp.int32(moved.shape[0]), jnp.sum(valid_flat.astype(jnp.int32)),
              jnp.sum(moved.astype(jnp.int32)))
    return glabel, SuffStats(sizes=sizes, centers=centers, sse=sse), counts


def uneven_sites(seed, n_sites=4, n=240, n_comp=4, b=4):
    """Overlapping planted 2-D clusters split so that the sites' live
    candidate counts differ: true cluster 2 is absent from site 1, and site
    2 holds fewer than b points of true cluster 3.  Each site's
    sub-clusters are its points' true clusters, so the global clusters
    are the true ones."""
    pts, lab = planted(seed=seed, n=4 * n_sites * n, n_comp=n_comp, spread=3.0, sigma=1.0)
    rng = np.random.default_rng(seed)
    xs, assigns = [], []
    for i in range(n_sites):
        few = np.flatnonzero(lab == 3)[: b - 2] if i == 2 else np.zeros(0, int)
        pool = np.flatnonzero(lab != {1: 2, 2: 3}.get(i, -1))
        pick = np.concatenate([few, rng.choice(pool, n - len(few), replace=False)])
        xs.append(pts[pick])
        assigns.append(lab[pick].astype(np.int32))
    xs, assigns = jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(assigns))
    stats = jax.vmap(lambda x, a: stats_from_assignment(x, a, n_comp))(xs, assigns)
    slots = assigns + (jnp.arange(n_sites, dtype=jnp.int32) * n_comp)[:, None]
    return xs, slots, stats


@pytest.mark.parametrize("seed", [0, 1])
def test_the_live_loop_equals_the_full_scan_bit_for_bit(seed):
    b, n_comp = 4, 4
    xs, slots, stats = uneven_sites(seed, n_comp=n_comp, b=b)
    cfg = VClusterConfig(k_local=n_comp, threshold_factor=0.5)
    merged = merge_gathered(stats, cfg)
    other = merge_gathered(stats, cfg._replace(criterion="merged_var", threshold_factor=2.0))
    assert int(merged.n_global) == n_comp
    # the sites' live counts differ: an absent cluster, and one below b
    glabel = np.asarray(merged.labels)[np.asarray(slots)]
    alive = np.flatnonzero(np.asarray(merged.stats.sizes) > 0)
    per_site = np.stack([np.bincount(g, minlength=merged.stats.n_slots)[alive] for g in glabel])
    assert (per_site == 0).any() and ((per_site > 0) & (per_site < b)).any()
    assert len(set(np.minimum(per_site, b).sum(axis=1))) > 1

    def equal(a, b_):
        return all(np.array_equal(np.asarray(u), np.asarray(v)) for u, v in zip(a, b_, strict=True))

    def got(labels, st, c):
        return labels, *st, c.steps, c.live, c.trips, c.moved

    def want(labels, st, c):  # the full scan's, whose trips would be its live ones
        steps, live, moved = c
        return labels, *st, steps, live, live, moved

    moved = 0
    for m in (merged, other):
        # site by site
        for i in range(len(xs)):
            w = full_scan_perturb(xs[i], slots[i], m, b)
            assert equal(got(*perturb_site(xs[i], slots[i], m, b)), want(*w))
            moved += int(w[2][2])
        # the batched loop, where each site stops at its own live count
        w = want(*jax.vmap(lambda x, s: full_scan_perturb(x, s, m, b))(xs, slots))
        assert equal(got(*jax.jit(jax.vmap(lambda x, s: perturb_site(x, s, m, b)))(xs, slots)), w)
        labels, counts = _perturb_batch(xs, slots, m, b)
        assert equal(got(labels, (), counts), w[:1] + w[4:])
    assert moved > 0
    # one merge result per member: each site under both merges in one wave
    assert not np.array_equal(np.asarray(merged.labels), np.asarray(other.labels))
    both = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *([merged] * len(xs) + [other] * len(xs)))
    labels, counts = _perturb_batch_many(jnp.concatenate([xs, xs]), jnp.concatenate([slots, slots]), both, b)
    w = [want(*full_scan_perturb(xs[i], slots[i], m, b)) for m in (merged, other) for i in range(len(xs))]
    w = [np.stack(col) for col in zip(*w, strict=True)]
    assert equal(got(labels, (), counts), w[:1] + w[4:])
