"""Sufficient statistics for variance-based distributed clustering.

The paper's key asymmetry: a site never ships data points, only the triple
(size N, center c, within-cluster SSE ``var``) per sub-cluster.  All global
decisions (merging, perturbation bookkeeping) are derivable from these.

Formulas (paper §3.1):

    N_new  = N_i + N_j
    c_new  = (N_i c_i + N_j c_j) / N_new
    var_new = var_i + var_j + s(i, j)
    s(i,j) = (N_i N_j) / (N_i + N_j) * d(c_i, c_j)^2

``var`` is the within-cluster *sum of squared distances* (SSE), which is
additive under the union formula above — this is what makes "logical
merging" possible with zero data movement.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SuffStats(NamedTuple):
    """Per-(sub)cluster sufficient statistics, vectorised over M slots.

    sizes:   (M,)   float32 — number of points (0 marks a dead/empty slot)
    centers: (M, D) float32 — centroid
    sse:     (M,)   float32 — within-cluster sum of squared distances ("var")
    """

    sizes: jax.Array
    centers: jax.Array
    sse: jax.Array

    @property
    def n_slots(self) -> int:
        return self.sizes.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[-1]


def stats_from_assignment(x: jax.Array, assign: jax.Array, k: int) -> SuffStats:
    """Compute per-cluster sufficient statistics from an assignment vector.

    x: (N, D); assign: (N,) int in [0, k).  Returns SuffStats with M = k.
    Empty clusters get size 0, center 0, sse 0.
    """
    n, d = x.shape
    one = jnp.ones((n,), dtype=jnp.float32)
    sizes = jax.ops.segment_sum(one, assign, num_segments=k)
    sums = jax.ops.segment_sum(x.astype(jnp.float32), assign, num_segments=k)
    safe = jnp.maximum(sizes, 1.0)
    centers = sums / safe[:, None]
    # SSE via E[|x|^2] - |c|^2 * N  (one pass, numerically fine in f32 for
    # the data scales used here; tests cross-check against direct form).
    sqsum = jax.ops.segment_sum(
        jnp.sum(x.astype(jnp.float32) ** 2, axis=-1), assign, num_segments=k
    )
    sse = sqsum - sizes * jnp.sum(centers**2, axis=-1)
    sse = jnp.maximum(sse, 0.0)  # clamp negative rounding residue
    return SuffStats(sizes=sizes, centers=centers, sse=sse)


def merge_cost(stats: SuffStats) -> jax.Array:
    """Pairwise variance increase s(i,j) for every slot pair.

    Returns (M, M) float32; s(i,j) = N_i N_j/(N_i+N_j) * ||c_i - c_j||^2.
    Dead slots (size 0) produce +inf rows/cols; diagonal is +inf.
    """
    sizes, centers = stats.sizes, stats.centers
    m = sizes.shape[0]
    d2 = pairwise_sq_dists(centers, centers)
    denom = sizes[:, None] + sizes[None, :]
    s = jnp.where(denom > 0, (sizes[:, None] * sizes[None, :]) / jnp.maximum(denom, 1e-30) * d2, jnp.inf)
    alive = sizes > 0
    mask = alive[:, None] & alive[None, :] & ~jnp.eye(m, dtype=bool)
    return jnp.where(mask, s, jnp.inf)


def merge_stats(stats: SuffStats, i: jax.Array, j: jax.Array) -> SuffStats:
    """Merge slot j into slot i (paper's update formulas); slot j dies."""
    ni, nj = stats.sizes[i], stats.sizes[j]
    ci, cj = stats.centers[i], stats.centers[j]
    n_new = ni + nj
    w = jnp.where(n_new > 0, 1.0 / jnp.maximum(n_new, 1e-30), 0.0)
    c_new = (ni * ci + nj * cj) * w
    s_ij = jnp.where(n_new > 0, ni * nj * w * jnp.sum((ci - cj) ** 2), 0.0)
    sse_new = stats.sse[i] + stats.sse[j] + s_ij
    sizes = stats.sizes.at[i].set(n_new).at[j].set(0.0)
    centers = stats.centers.at[i].set(c_new).at[j].set(0.0)
    sse = stats.sse.at[i].set(sse_new).at[j].set(0.0)
    return SuffStats(sizes=sizes, centers=centers, sse=sse)


def pairwise_sq_dists(a: jax.Array, b: jax.Array) -> jax.Array:
    """(Na, D), (Nb, D) -> (Na, Nb) squared euclidean distances.

    MXU-friendly form |a|^2 + |b|^2 - 2 a.b^T (same identity the Pallas
    kernel uses); clamped at 0 against rounding.  The matmul runs at
    ``Precision.HIGHEST``: at TPU's default (one bf16 pass) nearest-centre
    ranks would differ from the kernel's f32 contraction.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a**2, axis=-1)[:, None]
    b2 = jnp.sum(b**2, axis=-1)[None, :]
    d2 = a2 + b2 - 2.0 * jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d2, 0.0)


def stack_site_stats(per_site: SuffStats) -> SuffStats:
    """Flatten per-site stats (s, k, ...) into a single (s*k, ...) slot array.

    Slot index encodes the paper's ``cluster_{i,number}`` unique id:
    slot = site * k + number.
    """
    s, k = per_site.sizes.shape
    return SuffStats(
        sizes=per_site.sizes.reshape(s * k),
        centers=per_site.centers.reshape(s * k, -1),
        sse=per_site.sse.reshape(s * k),
    )


def total_sse(stats: SuffStats) -> jax.Array:
    """Global clustering objective: sum of within-cluster SSE over live slots."""
    return jnp.sum(jnp.where(stats.sizes > 0, stats.sse, 0.0))


def stats_bytes(stats: SuffStats) -> int:
    """Communication payload of shipping these stats (paper's comm model).

    4 bytes/float: N + D (center) + SSE per slot.
    """
    m, d = stats.centers.shape
    return int(m * (1 + d + 1) * 4)
