"""The plain reference of the paper's variance-based distributed
clustering (Algorithm 1), written from the semantics alone.

It imports nothing of the program and takes nothing it made: only the
points the benchmark generated and the request's parameters.  It
restates the algorithm as the paper and the program define it:

1. the site split: one ``np.random.default_rng(split_seed).permutation``
   of the points, truncated to whole sites, cut into ``n_sites`` equal
   shards in order;
2. per site, the key ``jax.random.split(PRNGKey(seed), n_sites)[site]``;
   k-means++ seeding with the same ``jax.random`` calls (the first centre
   by ``randint`` on the site key, then per centre one ``split`` of the
   carried key and one ``choice`` weighted by each point's squared
   distance to its nearest chosen centre);
3. ``iters`` Lloyd steps: nearest centre (first on ties), means of the
   non-empty clusters, an empty cluster keeps its centre and the first
   empty one is re-seeded at the point farthest from its centre; then the
   final assignment and each sub-cluster's size, mean and SSE;
4. the logical merge over all sites' sub-clusters (slot = site * k +
   number): while the smallest variance increase
   s(i, j) = N_i N_j / (N_i + N_j) |c_i - c_j|^2 over live pairs is below
   tau = factor x the largest sub-cluster SSE, merge the pair (the lower
   slot survives, so a global cluster is named by its lowest slot);
5. the border perturbation, per site on its own points against its copy
   of the global statistics: the ``b`` points of the site farthest from
   their global centre in each global cluster (ties by position), taken
   cluster by cluster, farthest first; each moves to the nearest other
   global cluster when N_j/(N_j+1) d_j^2 < N_g/(N_g-1) d_g^2, and the
   statistics are updated after each move.

Departures, each deliberate:

* Lloyd's distances are sum((x - c)^2), computed directly in float32 on
  the device, a block of sites at a time (no matrix product, so the TPU's
  default one-pass bfloat16 matrix precision never enters); the program
  uses |x|^2 + |c|^2 - 2 x.c.  Points nearly tied between two centres may
  go to the other one: ``subcluster_size_gap`` reads that.
* The seeding's distances use |x|^2 + |c|^2 - 2 x.c at
  ``Precision.HIGHEST``, as the program's seeding does, over all sites in
  one vmapped call.  k-means++ is chaotic: the two forms differ by about
  1e-4 of a point's distance to a nearby chosen centre, which moved a
  draw at 2 of 40 sites of 25,000 points (on the CPU), and a moved draw
  re-partitions that site's sub-clusters.  So the seeding keeps the
  program's arithmetic; only the draws' arguments are this file's.
* Each sub-cluster's size, mean and SSE, the merge and the perturbation
  run in float64 on the host, with SSE as the direct sum of squares (the
  program computes it as sum |x|^2 - N |c|^2 in float32).

``dtype`` is the control's knob: "bfloat16" holds the points in
bfloat16, the precision below the float32 the configuration states:
every distance of the seeding and of Lloyd's steps is computed from
points and centres in bfloat16, and the sub-cluster statistics (and so
the merge and the perturbation) from the points rounded to bfloat16.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

SITE_BLOCK = 25  # sites a Lloyd call holds on the device at once


@dataclass
class Clustering:
    labels: np.ndarray  # (s, n) global cluster (lowest slot) of each point, after perturbation
    sizes: np.ndarray  # (s, k) sub-cluster sizes
    roots: np.ndarray  # (G,) the global clusters' slots
    g_sizes: np.ndarray  # (G,) merged sizes, before perturbation
    g_centres: np.ndarray  # (G, D)
    g_sse: np.ndarray  # (G,)
    n_merges: int
    n_moved: int  # points the border perturbation moved, all sites

    @property
    def n_global(self) -> int:
        return len(self.roots)


def split(points: np.ndarray, n_sites: int, split_seed: int) -> np.ndarray:
    """(s, n, D): one seeded permutation, truncated to whole sites."""
    idx = np.random.default_rng(split_seed).permutation(len(points))
    n = len(points) // n_sites
    return points[idx[: n * n_sites]].reshape(n_sites, n, points.shape[1])


@functools.lru_cache(maxsize=4)
def _seeding(k: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def sq_dists(x, c):
        if dtype == "float32":
            x2 = jnp.sum(x * x, axis=-1)[:, None]
            c2 = jnp.sum(c * c, axis=-1)[None, :]
            xc = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
            return jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)
        return _direct(x, c, dtype)

    def one(key, x):
        n, d = x.shape
        first = jax.random.randint(key, (), 0, n)
        c0 = jnp.zeros((k, d), jnp.float32).at[0].set(x[first])

        def body(i, carry):
            c, key = carry
            key, sub = jax.random.split(key)
            d2 = jnp.where((jnp.arange(k) < i)[None, :], sq_dists(x, c), jnp.inf)
            near = jnp.min(d2, axis=-1)
            p = near / jnp.maximum(jnp.sum(near), 1e-30)
            return c.at[i].set(x[jax.random.choice(sub, n, p=p)]), key

        return jax.lax.fori_loop(1, k, body, (c0, key))[0]

    return jax.jit(jax.vmap(one))


def _direct(x, c, dtype: str):
    """(n, k) sum((x - c)^2) in ``dtype``, as float32."""
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    diff = x.astype(dt)[:, None, :] - c.astype(dt)[None, :, :]
    return jnp.sum(diff * diff, axis=-1, dtype=dt).astype(jnp.float32)


@functools.lru_cache(maxsize=4)
def _lloyd(k: int, iters: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def assign(x, c):
        d2 = _direct(x, c, dtype)
        a = jnp.argmin(d2, axis=-1).astype(jnp.int32)
        return a, jnp.take_along_axis(d2, a[:, None], axis=-1)[:, 0]

    def one(x, c):
        n = x.shape[0]

        def step(_, c):
            a, near = assign(x, c)
            sizes = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), a, num_segments=k)
            sums = jax.ops.segment_sum(x, a, num_segments=k)
            new = jnp.where((sizes > 0)[:, None], sums / jnp.maximum(sizes, 1.0)[:, None], c)
            empty = sizes == 0
            return jnp.where(jnp.any(empty), new.at[jnp.argmax(empty)].set(x[jnp.argmax(near)]),
                             new)

        return assign(x, jax.lax.fori_loop(0, iters, step, c))[0]

    return jax.jit(jax.vmap(one))


def sub_clusters(xs: np.ndarray, *, k: int, iters: int, seed: int,
                 dtype: str = "float32") -> np.ndarray:
    """(s, n) int32: each site's final assignment to its k sub-clusters."""
    import jax
    import jax.numpy as jnp

    s = xs.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), s)
    dev = jnp.asarray(xs)
    c0 = _seeding(k, dtype)(keys, dev)
    lloyd = _lloyd(k, iters, dtype)
    out = [np.asarray(lloyd(dev[b:b + SITE_BLOCK], c0[b:b + SITE_BLOCK]))
           for b in range(0, s, SITE_BLOCK)]
    return np.concatenate(out)


def site_stats(xs: np.ndarray, assign: np.ndarray, k: int):
    """(sizes (s, k), means (s, k, D), SSE (s, k)) in float64; an empty
    sub-cluster has size, mean and SSE 0."""
    s, n, d = xs.shape
    sizes = np.zeros((s, k))
    centres = np.zeros((s, k, d))
    sse = np.zeros((s, k))
    for i in range(s):
        x = xs[i].astype(np.float64)
        a = assign[i]
        sizes[i] = np.bincount(a, minlength=k)
        for j in range(d):
            centres[i, :, j] = np.bincount(a, weights=x[:, j], minlength=k)
        centres[i] /= np.maximum(sizes[i], 1.0)[:, None]
        sse[i] = np.bincount(a, weights=((x - centres[i][a]) ** 2).sum(1), minlength=k)
    return sizes, centres, sse


def merge(sizes: np.ndarray, centres: np.ndarray, sse: np.ndarray, factor: float):
    """The greedy logical merge over M slots (float64).  Returns (slot ->
    root (M,), merged sizes, centres, SSE (zero off the roots), merges)."""
    m = len(sizes)
    sizes, centres, sse = sizes.astype(np.float64), centres.astype(np.float64), sse.astype(np.float64)
    live = sizes > 0
    tau = factor * sse[live].max()
    label = np.arange(m)

    def row(i):
        """s(i, .) over every slot: inf on dead slots and on i."""
        diff = centres - centres[i]
        r = sizes[i] * sizes / (sizes[i] + sizes) * np.einsum("ij,ij->i", diff, diff)
        r[~live] = np.inf
        r[i] = np.inf
        return r

    cost = np.full((m, m), np.inf)
    for i in np.flatnonzero(live):
        cost[i] = row(i)
    best = cost.argmin(1)
    merges = 0
    while True:
        i = int(np.argmin(cost[np.arange(m), best]))
        j = int(best[i])
        if not cost[i, j] < tau:
            break
        i, j = min(i, j), max(i, j)  # the lower slot survives
        n = sizes[i] + sizes[j]
        s_ij = sizes[i] * sizes[j] / n * ((centres[i] - centres[j]) ** 2).sum()
        centres[i] = (sizes[i] * centres[i] + sizes[j] * centres[j]) / n
        sse[i] += sse[j] + s_ij
        sizes[i], sizes[j], sse[j] = n, 0.0, 0.0
        centres[j] = 0.0
        live[j] = False
        label[label == label[j]] = label[i]
        merges += 1
        cost[j] = np.inf
        cost[:, j] = np.inf
        cost[i] = row(i)
        cost[:, i] = cost[i]
        best[i] = cost[i].argmin()
        stale = np.flatnonzero(((best == i) | (best == j)) & live)
        best[stale] = cost[stale].argmin(1)
        better = live & (cost[:, i] < cost[np.arange(m), best])
        best[better] = i
    return label, sizes, centres, sse, merges


def perturb(x: np.ndarray, glabel: np.ndarray, g_sizes: np.ndarray, g_centres: np.ndarray,
            b: int) -> tuple[np.ndarray, int]:
    """One site's border perturbation (float64).  ``glabel`` (n,) is each
    point's global cluster, as an index into the (G,) global statistics;
    returns the labels after the moves, and how many points moved."""
    x = x.astype(np.float64)
    glabel = glabel.copy()
    sizes, centres = g_sizes.copy(), g_centres.copy()
    own = ((x - centres[glabel]) ** 2).sum(1)
    order = np.lexsort((np.arange(len(x)), -own, glabel))
    candidates = [order[glabel[order] == g][:b] for g in range(len(sizes))]
    moved = 0
    for mine in candidates:
        for idx in mine:
            gi = glabel[idx]
            d2 = ((centres - x[idx]) ** 2).sum(1)
            d2[sizes <= 0] = np.inf
            dg2 = d2[gi]
            d2[gi] = np.inf
            j = int(np.argmin(d2))
            ng, nj = sizes[gi], sizes[j]
            if ng <= 1 or not np.isfinite(d2[j]):
                continue
            gain = ng / (ng - 1.0) * dg2
            cost = nj / (nj + 1.0) * d2[j]
            if cost < gain:
                centres[gi] = (ng * centres[gi] - x[idx]) / (ng - 1.0)
                centres[j] = (nj * centres[j] + x[idx]) / (nj + 1.0)
                sizes[gi] -= 1.0
                sizes[j] += 1.0
                glabel[idx] = j
                moved += 1
    return glabel, moved


def vcluster(points: np.ndarray, *, n_sites: int, k_local: int, iters: int, seed: int,
             split_seed: int, b: int = 8, factor: float = 2.0,
             dtype: str = "float32") -> Clustering:
    """Algorithm 1 over ``points`` (N, D) as the request ``(k_local, iters,
    seed, split_seed)`` on ``n_sites`` sites asks for it."""
    xs = split(points, n_sites, split_seed)
    s, n, d = xs.shape
    assign = sub_clusters(xs, k=k_local, iters=iters, seed=seed, dtype=dtype)
    if dtype != "float32":  # the points as the control holds them
        import jax.numpy as jnp

        xs = xs.astype(jnp.dtype(dtype)).astype(np.float32)
    sizes, centres, sse = site_stats(xs, assign, k_local)
    label, g_sizes, g_centres, g_sse, merges = merge(
        sizes.reshape(-1), centres.reshape(-1, d), sse.reshape(-1), factor)
    roots = np.flatnonzero(g_sizes > 0)
    index = np.full(len(label), -1)
    index[roots] = np.arange(len(roots))
    labels = np.empty((s, n), np.int64)
    n_moved = 0
    for i in range(s):
        g = index[label[assign[i] + i * k_local]]
        got, moved = perturb(xs[i], g, g_sizes[roots], g_centres[roots], b)
        labels[i] = roots[got]
        n_moved += moved
    return Clustering(labels=labels, sizes=sizes, roots=roots, g_sizes=g_sizes[roots],
                      g_centres=g_centres[roots], g_sse=g_sse[roots], n_merges=merges,
                      n_moved=n_moved)
