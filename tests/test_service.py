"""MiningService behavior: submit/poll/result lifecycle, versioned cache
(hits never cross a dataset version), request coalescing, admission
control, weighted round-robin fairness, and the per-request/per-tenant
ledger."""

from __future__ import annotations

import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from repro.testing import given, settings, strategies as st

from repro.core.apriori import concat_dbs, local_apriori
from repro.launch.serve import MiningService, fairness_violations
from repro.workflow.registry import workloads
from repro.workflow.requests import QueueFullError, TenantQueues


def _tx_batch(seed: int, n_tx: int = 40, n_items: int = 8) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.random((n_tx, n_items)) < 0.45


def _service(**kw) -> MiningService:
    kw.setdefault("count_backend", "jnp")
    kw.setdefault("use_kernel", False)
    kw.setdefault("n_sites", 2)
    svc = MiningService(**kw)
    svc.register_dataset("tx", "transactions", n_items=8)
    svc.append_transactions("tx", _tx_batch(0))
    return svc


def test_submit_poll_result_lifecycle():
    svc = _service()
    rid = svc.submit("alice", "apriori", "tx", {"k": 3, "minsup": 0.2})
    assert svc.poll(rid) == "queued"
    with pytest.raises(RuntimeError, match="queued"):
        svc.result(rid)
    done = svc.step()
    assert done == [rid]
    assert svc.poll(rid) == "done"
    res = svc.result(rid)
    assert res.frequent[1]  # something is frequent at minsup 0.2
    req = svc.request(rid)
    assert req.dataset_version == 1
    assert req.backend == "batched"
    assert not req.cache_hit
    assert req.service_s >= req.queue_wait_s >= 0.0


def test_validation_errors():
    svc = _service()
    with pytest.raises(KeyError, match="register_dataset"):
        svc.submit("a", "apriori", "nope")
    with pytest.raises(ValueError, match="unknown app"):
        svc.submit("a", "word2vec", "tx")
    with pytest.raises(ValueError, match="points dataset"):
        svc.submit("a", "kmeans", "tx")
    with pytest.raises(ValueError, match="already registered"):
        svc.register_dataset("tx", "transactions", n_items=8)


def test_cache_hit_on_repeated_query():
    svc = _service()
    r1 = svc.submit("alice", "apriori", "tx", {"k": 3, "minsup": 0.2})
    svc.step()
    r2 = svc.submit("bob", "apriori", "tx", {"minsup": 0.2, "k": 3})  # reordered params
    svc.step()
    assert svc.cache.stats.hits == 1
    assert svc.executions == 1
    req2 = svc.request(r2)
    assert req2.cache_hit and req2.backend == "cache" and req2.compute_s == 0.0
    assert svc.result(r2) is svc.result(r1)


def test_cache_never_serves_across_versions():
    svc = _service()
    r1 = svc.submit("alice", "apriori", "tx", {"k": 3, "minsup": 0.2})
    svc.step()
    svc.append_transactions("tx", _tx_batch(1))
    r2 = svc.submit("alice", "apriori", "tx", {"k": 3, "minsup": 0.2})
    svc.step()
    req1, req2 = svc.request(r1), svc.request(r2)
    assert (req1.dataset_version, req2.dataset_version) == (1, 2)
    assert not req2.cache_hit  # the append made the old entry unreachable
    assert svc.cache.stats.hits == 0
    assert svc.result(r2).counts != svc.result(r1).counts


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=5_000))
def test_served_results_always_match_current_version(seed):
    """Interleave appends and repeated queries: every served result —
    cached or computed — equals from-scratch Apriori over the data as of
    the request's dataset_version (i.e. cache hits are never stale)."""
    rng = np.random.default_rng(seed)
    svc = MiningService(count_backend="jnp", use_kernel=False)
    svc.register_dataset("tx", "transactions", n_items=6)
    svc.append_transactions("tx", rng.random((int(rng.integers(5, 20)), 6)) < 0.5)
    for _ in range(4):
        if rng.random() < 0.5:
            svc.append_transactions("tx", rng.random((int(rng.integers(3, 15)), 6)) < 0.5)
        params = {"k": int(rng.integers(1, 4)), "min_count": int(rng.integers(1, 8))}
        rid = svc.submit("t0", "apriori", "tx", params)
        svc.step()
        got = svc.result(rid)
        state = svc._datasets["tx"].delta
        scratch = local_apriori(concat_dbs(state._batches), params["k"], params["min_count"])
        assert got.counts == scratch.counts
        assert got.frequent == scratch.frequent
    assert svc.cache.stats.hits + svc.cache.stats.misses == 4


def test_coalescing_identical_requests_one_execution():
    svc = _service()
    rids = [svc.submit(t, "apriori", "tx", {"k": 2, "minsup": 0.3})
            for t in ("a", "b", "c")]
    done = svc.step(max_requests=8)
    assert sorted(done) == sorted(rids)
    assert svc.executions == 1
    assert svc.coalesced == 2
    rep = svc.request(rids[0])
    assert rep.coalesced_into is None
    for rid in rids[1:]:
        assert svc.request(rid).coalesced_into == rids[0]
        assert svc.result(rid) is svc.result(rids[0])
    # a request with DIFFERENT params must not coalesce
    r4 = svc.submit("a", "apriori", "tx", {"k": 2, "minsup": 0.5})
    r5 = svc.submit("b", "apriori", "tx", {"k": 2, "minsup": 0.3})
    svc.step(max_requests=8)
    assert svc.request(r4).coalesced_into is None
    assert not svc.request(r4).cache_hit
    assert svc.request(r5).cache_hit  # same version+params as the first wave


def test_admission_control_bounded_queues():
    svc = _service(max_depth=2)
    svc.submit("a", "apriori", "tx", {"k": 1, "minsup": 0.9})
    svc.submit("a", "apriori", "tx", {"k": 1, "minsup": 0.8})
    with pytest.raises(QueueFullError, match="full"):
        svc.submit("a", "apriori", "tx", {"k": 1, "minsup": 0.7})
    assert svc.queues.rejected == 1
    led = svc.ledger()
    assert led["rejected"] == 1
    assert led["per_tenant"]["a"]["rejected"] == 1
    # other tenants are unaffected by a's full queue
    svc.submit("b", "apriori", "tx", {"k": 1, "minsup": 0.9})
    assert svc.queues.depth("b") == 1


def test_round_robin_fairness_bound():
    svc = _service()
    tenants = ["t0", "t1", "t2"]
    for i in range(4):
        for t in tenants:
            svc.submit(t, "apriori", "tx", {"k": 1, "min_count": i + 1})
    svc.drain(max_requests=5)
    assert len(svc.pick_log) == 12
    assert fairness_violations(svc.pick_log, tenants, len(svc.pick_log)) == []


def test_weighted_fairness_shares():
    q = TenantQueues(max_depth=32, weights={"big": 2.0, "small": 1.0})
    from repro.workflow.requests import MiningRequest

    for i in range(6):
        q.push(MiningRequest(request_id=i, tenant="big", app="apriori", dataset="d"))
    for i in range(3):
        q.push(MiningRequest(request_id=100 + i, tenant="small", app="apriori", dataset="d"))
    picks = [q.pick().tenant for _ in range(9)]
    assert picks == ["big", "big", "small"] * 3  # 2:1 weighted cycles
    assert q.pick() is None


def test_fractional_weights_honor_ratios():
    """Weights below 1 are normalized at construction (divide by the
    smallest), so {big: 1, small: 0.5} grants the SAME 2:1 shares as
    {big: 2, small: 1} — fractional weights are no longer silently
    rounded up to one pick per cycle."""
    q = TenantQueues(max_depth=32, weights={"big": 1.0, "small": 0.5})
    assert q.weights == {"big": 2.0, "small": 1.0}
    from repro.workflow.requests import MiningRequest

    for i in range(6):
        q.push(MiningRequest(request_id=i, tenant="big", app="apriori", dataset="d"))
    for i in range(3):
        q.push(MiningRequest(request_id=100 + i, tenant="small", app="apriori", dataset="d"))
    picks = [q.pick().tenant for _ in range(9)]
    assert picks == ["big", "big", "small"] * 3
    assert q.pick() is None
    # weights >= 1 are untouched; non-positive weights still rejected
    assert TenantQueues(weights={"a": 3.0, "b": 1.0}).weights == {"a": 3.0, "b": 1.0}
    with pytest.raises(ValueError, match="must be > 0"):
        TenantQueues(weights={"a": 0.0})


def test_failed_request_does_not_kill_service():
    # n_sites=0 passes submit-time validation (a finite int) but blows up
    # at execution when the dataset is split — the "one bad request must
    # not kill the service" guard in _step
    svc = _service()
    bad = svc.submit("a", "gfm", "tx", {"k": 2, "minsup": 0.3, "n_sites": 0})
    ok = svc.submit("b", "apriori", "tx", {"k": 2, "minsup": 0.3})
    done = svc.step(max_requests=4)
    assert sorted(done) == sorted([bad, ok])
    assert svc.poll(bad) == "failed"
    with pytest.raises(RuntimeError, match="failed"):
        svc.result(bad)
    assert svc.poll(ok) == "done"
    assert svc.ledger()["per_tenant"]["a"]["failed"] == 1


def test_malformed_params_rejected_at_submit():
    """Non-finite and uncoercible params are LEDGERED rejections at
    submit — the params_key crash class (inf/nan killing the dispatch
    loop) is unreachable from a tenant request."""
    svc = _service()
    with pytest.raises(ValueError, match="non-finite"):
        svc.submit("a", "apriori", "tx", {"minsup": float("inf")})
    with pytest.raises(ValueError, match="non-finite"):
        svc.submit("a", "apriori", "tx", {"minsup": float("nan")})
    with pytest.raises(ValueError, match="expects int"):
        svc.submit("a", "apriori", "tx", {"min_count": "not-a-number"})
    with pytest.raises(ValueError, match="does not accept param"):
        svc.submit("a", "apriori", "tx", {"bogus": 1})
    led = svc.ledger()
    assert led["rejected"] == 4
    rejected = [r for r in led["requests"] if r["status"] == "rejected"]
    assert len(rejected) == 4 and all(r["error"] for r in rejected)
    assert led["per_tenant"]["a"]["rejected"] == 4
    # the dispatch loop is unharmed: a well-formed request still runs
    ok = svc.submit("a", "apriori", "tx", {"k": 2, "minsup": 0.3})
    assert svc.step() == [ok]
    assert svc.poll(ok) == "done"


def test_kmeans_warm_start_across_versions():
    svc = MiningService(count_backend="jnp", use_kernel=False)
    svc.register_dataset("pts", "points", dim=2)
    rng = np.random.default_rng(0)
    svc.append_points("pts", rng.normal(size=(60, 2)).astype(np.float32))
    r1 = svc.submit("a", "kmeans", "pts", {"k": 3, "iters": 8})
    svc.step()
    assert 3 in svc._datasets["pts"].warm_centers  # centroids retained
    svc.append_points("pts", rng.normal(loc=2.0, size=(30, 2)).astype(np.float32))
    r2 = svc.submit("a", "kmeans", "pts", {"k": 3, "iters": 8})
    svc.step()
    res1, res2 = svc.result(r1), svc.result(r2)
    assert res2.centers.shape == (3, 2)
    assert res2.assign.shape == (90,)
    assert not svc.request(r2).cache_hit  # version bumped between queries
    assert np.isfinite(float(res2.inertia)) and float(res1.inertia) >= 0.0


def _registry_tx_pool(n_sites: int) -> list[tuple[str, dict]]:
    """Every registered transactions workload's smoke params — the mixed
    trace is parametrized off the registry, so a newly registered app is
    exercised here with NO test change."""
    pool: list[tuple[str, dict]] = []
    for spec in workloads():
        if spec.dataset_kind != "transactions":
            continue
        for smoke in spec.smoke_params:
            params = dict(smoke)
            if spec.runner == "grid":
                params["n_sites"] = n_sites
            pool.append((spec.name, params))
    return pool


def test_mixed_tenant_trace_ledger():
    """A small mixed-tenant burst trace end-to-end on the batched
    backend, drawing every registered transactions app from the registry
    smoke params: everything completes, repeats hit the cache, identical
    concurrent requests coalesce, the fairness bound holds, and the
    ledger is JSON-serializable."""
    svc = _service()
    tenants = ["t0", "t1", "t2"]
    pool = _registry_tx_pool(n_sites=2)
    assert {app for app, _ in pool} == {
        s.name for s in workloads() if s.dataset_kind == "transactions"
    }
    rng = np.random.default_rng(7)
    for burst in range(3):
        for t in tenants:
            # bursts 0 and 1 share one query at the same dataset version
            # (the append lands after burst 1): burst 0 executes it,
            # burst 1 coalesces AND cache-hits it deterministically
            app, params = pool[(burst // 2) % len(pool)]
            svc.submit(t, app, "tx", params)
            app, params = pool[int(rng.integers(len(pool)))]
            svc.submit(t, app, "tx", params)
        svc.drain(max_requests=6)
        if burst == 1:
            svc.append_transactions("tx", _tx_batch(burst + 10, n_tx=20))
    led = svc.ledger()
    assert len(led["requests"]) == 18
    assert all(r["status"] == "done" for r in led["requests"])
    assert led["cache"]["hits"] > 0
    assert led["coalesced"] > 0
    assert led["executions"] + led["cache"]["hits"] + led["coalesced"] == 18
    assert fairness_violations(svc.pick_log, tenants, len(svc.pick_log)) == []
    for t in tenants:
        assert led["per_tenant"][t]["submitted"] == 6
        assert led["per_tenant"][t]["done"] == 6
    json.dumps(led)  # the CI artifact must serialize


def test_ledger_records_shape():
    svc = _service()
    rid = svc.submit("a", "apriori", "tx", {"k": 2, "minsup": 0.3})
    svc.step()
    rec = next(r for r in svc.ledger()["requests"] if r["request_id"] == rid)
    for field in ("tenant", "app", "dataset", "dataset_version", "status",
                  "cache_hit", "coalesced_into", "backend", "queue_wait_s",
                  "compute_s", "service_s", "error"):
        assert field in rec
    assert rec["status"] == "done" and rec["error"] is None


def test_fused_fallback_is_ledgered():
    """A fused dispatch that throws falls back to serial per-group runs;
    the requests still complete, and the fallback is counted with its
    exception text instead of passing in silence."""
    svc = _service()

    def boom(*_a, **_kw):
        raise RuntimeError("fused dispatch refused")

    svc.runtime.run_many = boom
    rids = [svc.submit("a", "fdm", "tx", {"k": 2, "minsup": 0.3}),
            svc.submit("b", "fdm", "tx", {"k": 2, "minsup": 0.45})]
    svc.step(max_requests=4)
    assert all(svc.poll(r) == "done" for r in rids)
    led = svc.ledger()
    assert led["fused_fallbacks"] == 1 and led["fused_requests"] == 0
    assert led["fused_fallback_errors"] == ["RuntimeError: fused dispatch refused"]
    assert led["failures"] == 0
    json.dumps(led)


def test_cli_exits_nonzero_on_failed_requests(monkeypatch, capsys):
    """``python -m repro.launch.serve`` fails when any request failed,
    with or without ``--check``."""
    from repro.launch import serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    args = ["--requests", "6", "--tenants", "2", "--append-every", "0"]
    assert serve.main(args) == 0

    def boom(self, rep):
        raise RuntimeError("execution refused")

    monkeypatch.setattr(serve.MiningService, "_execute", boom)
    monkeypatch.setattr(serve.MiningService, "_execute_fused", boom)
    assert serve.main(args) == 1
    assert "requests failed: RuntimeError: execution refused" in capsys.readouterr().err
