"""Scalability in #sites (the grid dimension the paper cares about):
communication bytes and sync rounds vs s for both algorithms — clustering
comm grows O(s*k) (stats only) while data grows O(n); GFM rounds stay 2
at every scale while FDM stays k."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.core.apriori import TransactionDB
from repro.core.fdm import fdm_mine
from repro.core.gfm import gfm_mine
from repro.core.vclustering import VClusterConfig, vcluster_pooled
from repro.data.synthetic import gaussian_mixture, ibm_transactions, split_sites, split_transactions


def run():
    # clustering: fixed global data, growing sites
    pts, _ = gaussian_mixture(3, 64_000, 6, n_components=8, spread=15.0, sigma=0.7)
    for s in (2, 4, 8, 16):
        xs = split_sites(pts, s, seed=0)
        cfg = VClusterConfig(k_local=12, kmeans_iters=15)
        t0 = time.perf_counter()
        res = vcluster_pooled(jax.random.PRNGKey(0), jnp.asarray(xs), cfg)
        jax.block_until_ready(res.labels)
        dt = time.perf_counter() - t0
        row(f"vcluster_sites_{s}", dt, f"comm_bytes={int(res.comm_bytes)};n_global={int(res.merged.n_global)}")

    # itemsets: fixed global db, growing sites
    dense = ibm_transactions(seed=9, n_tx=12_000, n_items=64, avg_tx_len=8, n_patterns=16)
    for s in (2, 4, 8, 16):
        sites = [TransactionDB.from_dense(x) for x in split_transactions(dense, s, seed=0)]
        t0 = time.perf_counter()
        g = gfm_mine(sites, 4, 0.06)
        t_g = time.perf_counter() - t0
        t0 = time.perf_counter()
        f = fdm_mine(sites, 4, 0.06)
        t_f = time.perf_counter() - t0
        assert g.frequent == f.frequent
        row(
            f"gfm_sites_{s}", t_g,
            f"rounds={g.comm.rounds};bytes={g.comm.bytes_sent};fdm_rounds={f.comm.rounds};fdm_bytes={f.comm.bytes_sent};fdm_s={t_f:.3f}",
        )


if __name__ == "__main__":
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run()
