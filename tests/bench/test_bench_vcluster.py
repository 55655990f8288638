"""The clustering one-shot cell, tiny, through the whole harness on the CPU
(a sound run is correct; the bf16 control and each planted fault are
not), and its plain reference (``bench/vcluster_reference.py``) against
the generator's truth and against the program's pooled driver."""

from __future__ import annotations

import json
import time
from types import SimpleNamespace

import numpy as np
import pytest
from benchutil import ROOT, run_tiny, tiny_cell

from bench import spec, vcluster_reference
from bench.gen import dimsets
from bench.metrics import kmeans_work

CELL = "vcluster.oneshot"
CHECKS = {"failed_requests", "label_mismatch", "n_global_gap", "subcluster_size_gap",
          "stats_error"}
# the generator's clusters cut to 2-D and packed close, so that they
# overlap and the border perturbation moves points (at the cell's shape
# it moves none)
BORDER = {"dim": 2, "n_clusters": 4, "box": [0, 10], "sigma": 1.0}


def test_sound_run_is_correct_and_reports_its_metrics():
    res = run_tiny(CELL, seed=2**31 + 5)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == CHECKS
    assert res["attempted"] >= 1 and res["failed"] == 0
    # the cell reports the one-shot job time under the accepted job_s metric
    assert set(res["metrics"]) == {"setup_s", "job_s.itemsets"}
    assert res["checks"]["label_mismatch"]["value"] == 0


def test_traced_run_reads_the_clustering_spans():
    res = run_tiny(CELL, trace=True)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for name in ("cluster_share.vcluster", "merge_share.vcluster", "perturb_share.vcluster"):
        assert m[name]["spans"] >= 1 and m[name]["value"] > 0, name
    # 4 sites x 20 sub-clusters merge into the 16 clusters
    assert m["merge_share.vcluster"]["merges_per_job"] == 4 * 20 - 16
    live = m["perturb_live_share.vcluster"]
    # each site scans 80 slots x 8 candidates; each of the 16 clusters has
    # its 8 border points at every site
    assert live["steps"] % (4 * 80 * 8) == 0
    assert live["live"] * 80 == live["steps"] * 16
    assert live["value"] == pytest.approx(20.0)
    # well-separated clusters: no border point is nearer another cluster
    assert live["moved"] == 0
    # the service's shared layers read here as in the itemsets cell
    assert m["split_share.itemsets"]["spans"] >= 1
    assert m["engine_share.itemsets"]["spans"] >= 1
    assert 0 <= m["idle_share.itemsets"]["value"] <= 100
    assert m["compiles_per_job.itemsets"] is not None


@pytest.mark.parametrize("control", ["bf16", "answer_altered", "half_batch"])
def test_control_and_faults_are_not_correct(control):
    res = run_tiny(CELL, control=control)
    assert res["correct"] is False, (control, res["checks"])
    if control == "answer_altered":
        assert res["checks"]["label_mismatch"]["value"] >= 1
    if control == "bf16":  # bfloat16 points break the statistics too
        stats = res["checks"]["stats_error"]
        assert stats["value"] > stats["limit"]


def _run_border(seed: int, *, trace: bool = False, control: str | None = None) -> dict:
    from bench import harness

    cfg, mix = tiny_cell(CELL)
    cfg["data"].update(BORDER)
    return harness.run_cell(CELL, seed, 1.5, trace, t_start=time.perf_counter(),
                            require_tpu=False, config=cfg, traffic_mix=mix, control=control)


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_the_comparison_sees_the_border_perturbation_where_points_move(seed):
    sound = _run_border(seed, trace=True)
    assert sound["correct"] is True, sound["checks"]
    moved = sound["metrics"]["perturb_live_share.vcluster"]["moved"]
    # every job of the window is the same request, so each moves alike
    assert moved > 0 and moved % sound["attempted"] == 0
    skipped = _run_border(seed, control="perturbation_skipped")
    assert skipped["correct"] is False
    # the points the program's scan moved are the points the reference moved
    assert skipped["checks"]["label_mismatch"]["value"] == moved // sound["attempted"]


def _tiny_data() -> dict:
    cfg, _ = tiny_cell(CELL)
    return cfg["data"]


def _job(seed: int) -> dict:
    cfg, mix = tiny_cell(CELL)
    params = mix["apps"][0]["params"]
    return {"n_sites": cfg["service"]["n_sites"], "k_local": params["k_local"],
            "iters": params["iters"], "seed": seed, "split_seed": seed + 1}


@pytest.mark.parametrize("seed", [4, 2**31 + 9])
def test_the_reference_finds_the_generator_s_clusters(seed):
    data = _tiny_data()
    x, truth = dimsets.draw(data, seed)
    job = _job(seed)
    ref = vcluster_reference.vcluster(x, **job)
    site_truth = vcluster_reference.split(truth[:, None], job["n_sites"], job["split_seed"])[..., 0]
    # every global cluster is one true cluster, and all 16 are found
    assert ref.n_global == data["n_clusters"]
    assert ref.n_merges == job["n_sites"] * job["k_local"] - data["n_clusters"]
    pairs = set(zip(ref.labels.ravel().tolist(), site_truth.ravel().tolist()))
    assert len(pairs) == data["n_clusters"]
    # every sub-cluster is pure
    xs = vcluster_reference.split(x, job["n_sites"], job["split_seed"])
    assign = vcluster_reference.sub_clusters(xs, k=job["k_local"], iters=job["iters"],
                                             seed=job["seed"])
    slots = assign + np.arange(job["n_sites"])[:, None] * job["k_local"]
    assert len(set(zip(slots.ravel().tolist(), site_truth.ravel().tolist()))) == len(
        np.unique(slots))
    # moving every point by 1e-6 of the box changes no label
    lo, hi = data["box"]
    nudge = np.random.default_rng(seed).choice([-1.0, 1.0], x.shape) * 1e-6 * (hi - lo)
    moved = vcluster_reference.vcluster((x + nudge).astype(np.float32), **job)
    assert np.array_equal(moved.labels, ref.labels)


@pytest.mark.parametrize("seed", [6, 2**31 + 13])
def test_the_reference_agrees_with_the_pooled_driver(seed):
    import jax
    import jax.numpy as jnp

    from repro.core.vclustering import VClusterConfig, vcluster_pooled

    kind = spec.kind("clusters")
    x = dimsets.rows(_tiny_data(), seed)
    job = _job(seed)
    ref = vcluster_reference.vcluster(x, **job)
    xs = vcluster_reference.split(x, job["n_sites"], job["split_seed"])
    res = vcluster_pooled(jax.random.PRNGKey(job["seed"]), jnp.asarray(xs),
                          VClusterConfig(k_local=job["k_local"], kmeans_iters=job["iters"]))
    got = kind.checks_of(kind.host_answer("vclustering", res), ref, float(x.max() - x.min()))
    assert got["label_mismatch"] == 0 and got["n_global_gap"] == 0
    assert got["subcluster_size_gap"] <= kind.LIMITS["subcluster_size_gap"]
    assert got["stats_error"] <= kind.LIMITS["stats_error"]


def test_the_configuration_states_the_algorithm_the_program_and_reference_run():
    import inspect

    from repro.core.vclustering import VClusterConfig

    cell = spec.load_cell(CELL)
    algo = cell.config["algorithm"]
    params = cell.traffic["apps"][0]["params"]
    assert (params["k_local"], params["iters"]) == (algo["k_local"], algo["iters"])
    program = VClusterConfig()
    assert (program.border_candidates, program.threshold_factor, program.criterion) == (
        algo["border_candidates"], algo["threshold_factor"], algo["criterion"])
    ref = inspect.signature(vcluster_reference.vcluster).parameters
    assert (ref["b"].default, ref["factor"].default) == (algo["border_candidates"],
                                                          algo["threshold_factor"])


def test_the_generator_is_a_function_of_the_seed():
    data = _tiny_data()
    a, ta = dimsets.draw(data, 2**31 + 3)
    b, tb = dimsets.draw(data, 2**31 + 3)
    assert a.dtype == np.float32 and a.shape == (data["n_points"], data["dim"])
    assert np.array_equal(a, b) and np.array_equal(ta, tb)
    assert not np.array_equal(a, dimsets.rows(data, 2**31 + 4))
    # the run seed draws points around the deployment's fixed centres
    c = dimsets.centres(data)
    assert np.abs(a - c[ta]).max() < 8 * data["sigma"]


def test_kmeans_work_and_its_roofline_reader():
    ops, nbytes = kmeans_work.kmeans_assign(n=1000, k=20, d=32)
    assert ops == 3 * 1000 * 20 * 32
    assert nbytes == 4 * 1000 * 32 + 4 * 20 * 32 + 8 * 1000
    assert kmeans_work.kmeans_job(1000, 20, 32, iters=25) == (26 * ops, 26 * nbytes)

    read = spec.metric_reader("kmeans_assign_roofline")
    cell = spec.load_cell(CELL)
    peaks = spec.peaks("TPU v5 lite")
    trace = SimpleNamespace(kernel=lambda pattern: (26, 0.5))
    rows = [{"app": "vclustering", "ok": True,
             "params": {"k_local": 20, "iters": 25, "seed": 1, "split_seed": 2}}]
    got = read(SimpleNamespace(cell=cell, requests=rows, trace=trace, peaks=peaks))
    n = cell.config["data"]["n_points"]
    _, job_bytes = kmeans_work.kmeans_job(n, 20, cell.config["data"]["dim"], 25)
    assert got["bound"] == "memory" and got["jobs"] == 1 and got["events"] == 26
    assert got["value"] == pytest.approx(100 * job_bytes / 819e9 / 0.5)
    assert read(SimpleNamespace(cell=cell, requests=[], trace=trace, peaks=peaks)) is None


def test_the_cell_is_in_the_benchmark_as_new_entries():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w["chips"] == 1 and w["config"] == "dim032-k16-paper200"
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in mine} == {
        "cluster_share.vcluster", "merge_share.vcluster", "perturb_share.vcluster",
        "perturb_live_share.vcluster", "kmeans_assign_roofline",
        # accepted readers of shared layers, with the cell appended to their lists
        "idle_share.itemsets", "split_share.itemsets", "engine_share.itemsets",
        "compiles_per_job.itemsets"}
    assert [m["name"] for m in bench["end_to_end"]] == ["setup_s", "job_s.itemsets"]
    reported = {m["name"] for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert all(m["moves"] in reported for m in mine)
