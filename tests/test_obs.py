"""The program's spans and compile counter (``repro.obs``), read back from
a real profiler trace of ``MiningService`` runs on the CPU."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.data.synthetic import ibm_transactions
from repro.launch.serve import MiningService
from repro.obs import compiles
from repro.runtime.cache import ResultCache
from repro.workflow.registry import get_workload

APPS = ("gfm", "fdm", "cd_apriori")
PARAMS = {"k": 3, "minsup": 0.1, "split_seed": 7}
LEVEL = ("repro.level.join", "repro.level.stage", "repro.level.count",
         "repro.level.count1", "repro.level.fold")
EVERY_SPAN = {"repro.step", "repro.request", "repro.split", "repro.build", "repro.engine",
              "repro.job", "repro.job.ready", "repro.sync", *LEVEL}


def _service(n_tx=400, n_items=20, n_sites=4) -> MiningService:
    svc = MiningService(backend="batched", n_sites=n_sites)
    svc.register_dataset("tx", "transactions", n_items=n_items)
    svc.append_transactions("tx", ibm_transactions(0, n_tx, n_items))
    return svc


def _run(svc: MiningService) -> dict:
    """One request of each app, each in a step of its own; app -> id."""
    ids = {}
    for app in APPS:
        ids[app] = svc.submit("t", app, "tx", PARAMS)
        svc.drain()
        assert svc.poll(ids[app]) == "done", svc.request(ids[app]).error
    return ids


def _spans(log_dir: Path) -> list[dict]:
    """Every ``repro.`` event of the trace with its enclosing ``repro.``
    spans (innermost last), its stats and its host line."""
    path = sorted(log_dir.glob("**/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
                          for e in line.events if e.name.startswith("repro.")),
                         key=lambda e: (e[0], -e[1]))
            stack: list = []
            for s, e, name, stats in evs:
                while stack and stack[-1]["end"] <= s:
                    stack.pop()
                assert not stack or e <= stack[-1]["end"], f"{name} overlaps {stack[-1]['name']}"
                ev = {"name": name, "end": e, "stats": stats, "line": line.name,
                      "outer": [o["name"] for o in stack], "top": stack[0] if stack else None}
                out.append(ev)
                stack.append(ev)
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(untraced service, traced service, their ids, the trace's spans)."""
    plain = _service()
    plain_ids = _run(plain)
    svc = _service()
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        ids = _run(svc)
    return plain, svc, plain_ids, ids, _spans(log_dir)


def test_every_span_appears(traced):
    *_, spans = traced
    assert {s["name"] for s in spans} >= EVERY_SPAN


def test_spans_nest_from_step_down_to_the_levels(traced):
    *_, spans = traced
    chain = ["repro.step", "repro.request", "repro.engine", "repro.job"]
    for s in spans:
        outer = s["outer"]
        if s["name"] in LEVEL or s["name"] in ("repro.sync", "repro.job.ready"):
            assert [o for o in outer if o in chain] == chain, (s["name"], outer)
        elif s["name"] in ("repro.split", "repro.build", "repro.engine"):
            assert outer == ["repro.step", "repro.request"], (s["name"], outer)
        elif s["name"] == "repro.job":
            assert outer == ["repro.step", "repro.request", "repro.engine"]
        elif s["name"] == "repro.request":
            assert outer == ["repro.step"]
        elif s["name"] == "repro.step":
            assert outer == []


def test_each_request_span_names_its_app_and_holds_its_phases(traced):
    _, svc, _, ids, spans = traced
    requests = [s for s in spans if s["name"] == "repro.request"]
    assert [r["stats"]["app"] for r in requests] == list(APPS)
    assert [int(r["stats"]["request_ids"]) for r in requests] == [ids[a] for a in APPS]
    for req in requests:
        inside = {s["name"] for s in spans if s["top"] is req["top"] and "repro.request" in s["outer"]}
        assert inside >= {"repro.split", "repro.build", "repro.engine", "repro.job",
                          "repro.sync", *LEVEL}, (req["stats"]["app"], inside)
        # the service recorded the request's compiles on its span
        rid = int(req["stats"]["request_ids"])
        assert req["stats"]["compiles"] == svc.request(rid).compiles


def test_results_are_the_same_traced_and_untraced(traced):
    plain, svc, plain_ids, ids, _ = traced
    for app in APPS:
        digest = get_workload(app).digest
        assert digest(svc.result(ids[app])) == digest(plain.result(plain_ids[app])), app


def test_compiles_are_counted_per_request_and_in_the_ledger():
    # shapes no other test of this file uses, so the first run compiles
    svc = _service(n_tx=777, n_items=53, n_sites=3)
    c0 = compiles()
    first = svc.submit("t", "gfm", "tx", PARAMS)
    svc.drain()
    made = compiles() - c0
    svc.cache = ResultCache(svc.cache.capacity)  # the repeat must run, not hit the cache
    again = svc.submit("t", "gfm", "tx", PARAMS)
    svc.drain()
    assert svc.poll(first) == svc.poll(again) == "done"
    assert svc.request(first).compiles == made.count > 0
    assert svc.request(again).compiles == 0
    led = svc.ledger()
    assert led["compiles"] == made.count
    assert led["compile_s"] == pytest.approx(made.seconds) and led["compile_s"] > 0
    assert [r["compiles"] for r in led["requests"]] == [made.count, 0]


def test_a_vclustering_request_opens_its_stage_spans_with_their_stats(tmp_path):
    import numpy as np

    n_sites, k_local = 3, 4
    rng = np.random.default_rng(0)
    centres = np.array([[0.0, 0.0], [20.0, 0.0], [0.0, 20.0]])
    pts = (centres[rng.integers(0, 3, 300)] + rng.normal(0, 0.5, (300, 2))).astype(np.float32)
    svc = MiningService(backend="batched", n_sites=n_sites)
    svc.register_dataset("pts", "points", dim=2)
    svc.append_points("pts", pts)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        rid = svc.submit("t", "vclustering", "pts", {"k_local": k_local, "iters": 5})
        svc.drain()
    assert svc.poll(rid) == "done", svc.request(rid).error
    res = svc.result(rid)
    spans = _spans(tmp_path)
    stage = {s["name"]: s for s in spans if s["name"].startswith("repro.vc.")}
    assert set(stage) == {"repro.vc.cluster", "repro.vc.merge", "repro.vc.perturb"}
    for s in stage.values():
        assert s["outer"][:4] == ["repro.step", "repro.request", "repro.engine", "repro.job"]
    assert stage["repro.vc.merge"]["stats"]["merges"] == int(res.merged.n_merges) > 0
    perturb = stage["repro.vc.perturb"]["stats"]
    b = 8  # VClusterConfig.border_candidates
    assert perturb["steps"] == n_sites * n_sites * k_local * b
    # each of the global clusters has at least b points at every site
    assert perturb["live"] == n_sites * int(res.merged.n_global) * b
    # the loop steps through the live candidates only
    assert perturb["trips"] == perturb["live"]
    # well-separated clusters: no border point is nearer another cluster
    assert perturb["moved"] == 0
