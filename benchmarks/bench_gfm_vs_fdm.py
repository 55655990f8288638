"""Paper §5.2.1 / Table 3 (GFM & FDM rows): frequent-itemset mining on
synthetic transactions distributed over sites.

Paper setup: 4e6 transactions over 200 processes, sizes 1..4, GFM ~25%
faster than FDM with 2 communication passes instead of 4, FDM remote
support computation ≈13% of its compute.  We run a CPU-scaled instance
(same structure: uniform split, k=4) and report measured compute + the
grid-modeled times from the paper's own link matrix.
"""

from __future__ import annotations

import time

from benchmarks.common import row
from repro.core.apriori import TransactionDB
from repro.core.fdm import fdm_mine
from repro.core.gfm import gfm_mine
from repro.data.synthetic import ibm_transactions, split_transactions
from repro.workflow.overhead import GridModel, estimate_stages


def run(n_tx: int = 40_000, n_items: int = 96, n_sites: int = 8, k: int = 4, minsup: float = 0.05):
    dense = ibm_transactions(seed=42, n_tx=n_tx, n_items=n_items, avg_tx_len=10, n_patterns=24)
    sites = [TransactionDB.from_dense(s) for s in split_transactions(dense, n_sites, seed=0)]

    t0 = time.perf_counter()
    g = gfm_mine(sites, k, minsup)
    t_gfm = time.perf_counter() - t0

    t0 = time.perf_counter()
    f = fdm_mine(sites, k, minsup)
    t_fdm = time.perf_counter() - t0

    assert g.frequent == f.frequent, "GFM and FDM must agree exactly"

    # Raw local compute: GFM deliberately does MORE of it (no global
    # pruning); its win is in synchronization — exactly the paper's
    # framing ("avoid many synchronization and communication steps ...
    # rather than minimizing local execution times").  Report both.
    speedup = (t_fdm - t_gfm) / t_fdm * 100
    row("gfm_local_compute", t_gfm, f"rounds={g.comm.rounds};bytes={g.comm.bytes_sent};frequent={len(g.frequent)}")
    row("fdm_local_compute", t_fdm, f"rounds={f.comm.rounds};bytes={f.comm.bytes_sent};remote_frac={f.remote_count_time / max(f.total_count_time, 1e-9):.3f}")

    # grid-modeled TOTAL (paper's §5.2.2 estimation + per-round sync):
    # each synchronization round pays the worst Table-2 link for its
    # payload plus a per-round barrier (submit/matchmaking latency).
    model = GridModel()

    def grid_total(t_compute, comm, rounds):
        stages = [[(t_compute / n_sites, 0, 0, s) for s in range(n_sites)]]
        est = estimate_stages(stages, model)
        for r in range(rounds):
            per_round = comm.per_round_bytes[r] if r < len(comm.per_round_bytes) else 0
            est += model.worst_transfer_s(per_round // max(n_sites, 1))
            est += model.submit_latency_s * n_sites  # barrier re-dispatch
        return est

    tot_gfm = grid_total(t_gfm, g.comm, g.comm.rounds)
    tot_fdm = grid_total(t_fdm + f.remote_count_time, f.comm, f.comm.rounds)
    gain = (tot_fdm - tot_gfm) / tot_fdm * 100
    row("gfm_grid_total", tot_gfm, f"2 sync rounds, Table 2 links")
    row("fdm_grid_total", tot_fdm, f"{f.comm.rounds} sync rounds + remote-support recount")
    row("gfm_vs_fdm_grid_gain", tot_fdm - tot_gfm, f"pct={gain:.1f};paper=25pct (grid totals; raw-compute delta={speedup:.1f}pct)")
    assert tot_gfm < tot_fdm, "GFM must win once synchronization is priced in"
    return g, f


if __name__ == "__main__":
    from repro.launch.mesh import enable_compile_cache

    enable_compile_cache()
    run()
