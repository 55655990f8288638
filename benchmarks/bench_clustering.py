"""Paper §5.2.1 / Table 3 (V-Clustering row): variance-based distributed
clustering.

Paper setup: 5e7 samples over 200 processes, K-Means with 20 sub-clusters
per process, merge threshold 2x the largest sub-cluster variance; actual
compute ≈2% of the 1050 s grid wall time (the rest is middleware).  We
run a CPU-scaled instance, report the measured compute, the KB-scale
communication (the paper's key asymmetry) and the grid-modeled wall time
with the 295 s DAGMan prep latency -> the 98% overhead figure.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.core.vclustering import VClusterConfig, vcluster_pooled
from repro.data.synthetic import gaussian_mixture, split_sites
from repro.workflow.overhead import GridModel, estimate_stages, overhead_pct


def run(n_points: int = 200_000, dim: int = 8, n_sites: int = 8, k_local: int = 20):
    pts, _ = gaussian_mixture(7, n_points, dim, n_components=12, spread=20.0, sigma=0.8)
    xs = split_sites(pts, n_sites, seed=1)
    cfg = VClusterConfig(k_local=k_local, kmeans_iters=20, border_candidates=8)

    fn = jax.jit(lambda key, x: vcluster_pooled(key, x, cfg))
    key = jax.random.PRNGKey(0)
    xj = jnp.asarray(xs)
    res = fn(key, xj)  # compile + run
    jax.block_until_ready(res.labels)

    t0 = time.perf_counter()
    res = fn(key, xj)
    jax.block_until_ready(res.labels)
    t_compute = time.perf_counter() - t0

    data_bytes = xs.size * 4
    comm = int(res.comm_bytes)
    row(
        "vcluster_compute",
        t_compute,
        f"n_global={int(res.merged.n_global)};comm_bytes={comm};data_bytes={data_bytes};ratio={data_bytes / comm:.0f}x",
    )

    # grid model: the paper's Table 3 structure — local clustering stage +
    # merge stage vs the full engine with DAGMan prep.
    model = GridModel()
    est = estimate_stages(
        [
            [(t_compute / n_sites, xs[0].nbytes, comm // n_sites, s) for s in range(n_sites)],
            [(0.01, comm, 0, 0)],
        ],
        model,
    )
    measured = model.prep_latency_s + model.submit_latency_s * (n_sites + 1) + est
    ovh = overhead_pct(measured, est)
    row("vcluster_grid_estimated", est, "analytical lower bound")
    row("vcluster_grid_measured", measured, f"overhead_pct={ovh:.1f};paper=98pct")
    return res


if __name__ == "__main__":
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run()
