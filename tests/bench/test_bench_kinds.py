"""Generators and dataset kinds found by name: the Quest generator through
the lookup gives the rows it always gave; an unknown generator or kind is
an error that names the missing file; and a second dataset kind joins
the benchmark as new files alone, under a root of its own, and runs
through the whole harness on the CPU."""

from __future__ import annotations

import json
import shutil
import time

import numpy as np
import pytest
from benchutil import ROOT

from bench import deploy, spec
from bench.gen.quest import quest_patterns, quest_transactions

CONFIG = "bench/configs/quest-t10i4-n1000-paper200.json"


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_quest_rows_through_the_lookup_are_the_generator_s(seed):
    cfg = json.loads((ROOT / CONFIG).read_text())
    d = {**cfg["data"], **spec.generator("quest").TINY}
    cfg["data"] = d
    pats = quest_patterns(d["pattern_seed"], d["n_items"], d["n_patterns"],
                          d["avg_pattern_len"], correlation=d["correlation"],
                          corruption_mean=d["corruption_mean"],
                          corruption_var=d["corruption_var"])
    want = quest_transactions(seed, d["n_tx"], d["n_items"], pats, avg_tx_len=d["avg_tx_len"])
    got = deploy.make_rows(cfg, seed)
    assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("field,path", [("generator", "bench/gen/nope.py"),
                                        ("kind", "bench/kinds/nope.py")])
def test_an_unknown_generator_or_kind_names_the_missing_file(field, path):
    cfg = json.loads((ROOT / CONFIG).read_text())
    cfg["data"].update(spec.generator("quest").TINY)
    if field == "generator":
        cfg["data"]["generator"] = "nope"
    else:
        cfg["kind"] = "nope"
    with pytest.raises(spec.SpecError, match=path):
        deploy.build(cfg, 1)


# ---------------------------------------------------------------------------
# a second dataset kind, added as new files under a root of its own
# ---------------------------------------------------------------------------

BLOBS = '''
import numpy as np

TINY = {"n_points": 300}


def rows(data, seed):
    rng = np.random.default_rng(seed)
    centres = np.asarray(data["centres"], np.float64)
    which = rng.integers(0, len(centres), data["n_points"])
    noise = rng.normal(0.0, data["spread"], (data["n_points"], data["dim"]))
    return (centres[which] + noise).astype(np.float32)
'''

POINTS = '''
import numpy as np

from bench.checks import Check, patch

LIMITS = {"label_mismatch": 0}
CONTROLS = ()
KERNELS = {}


def load(svc, dataset, data, rows):
    svc.register_dataset(dataset, "points", dim=data["dim"])
    svc.append_points(dataset, rows)


def host_answer(app, res):
    if app == "kmeans":
        return {"assign": np.asarray(res.assign), "centers": np.asarray(res.centers)}
    raise ValueError(f"no comparison for app {app!r}")


def compare(rows, answers, control):
    x, worst = rows.astype(np.float64), 0
    for a in answers:
        c = a.value["centers"].astype(np.float64)
        nearest = np.argmin(((x[:, None, :] - c[None]) ** 2).sum(-1), axis=1)
        worst = max(worst, int((nearest != a.value["assign"]).sum()))
    return [Check("label_mismatch", worst, LIMITS["label_mismatch"])]


def _one_label_moved(orig):
    def f(*a, **kw):
        r = orig(*a, **kw)
        return r._replace(assign=r.assign.at[0].set((r.assign[0] + 1) % r.centers.shape[0]))
    return f


def _plant():
    from repro.core import kmeans

    return [patch(kmeans, "kmeans", _one_label_moved),
            patch(kmeans, "kmeans_warm", _one_label_moved)]


FAULTS = {"answer_altered": _plant}
'''

CELL = "blobs.kmeans"


@pytest.fixture(scope="module")
def points_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "bench/gen/blobs.py").write_text(BLOBS)
    (root / "bench/kinds/points.py").write_text(POINTS)
    (root / "bench/configs/blobs-3.json").write_text(json.dumps({
        "name": "blobs-3", "kind": "points", "dataset": "pts",
        "data": {"generator": "blobs", "n_points": 300, "dim": 2,
                 "centres": [[0, 0], [10, 0], [0, 10]], "spread": 0.5},
        "service": {"backend": "inline", "n_sites": 2}}))
    (root / "bench/traffic/kmeans-one.json").write_text(json.dumps({
        "clients": 1, "max_requests": 4, "rounds": 2,
        "apps": [{"app": "kmeans", "params": {"k": 3, "iters": 10}, "fresh": ["seed"]}]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "blobs-3", "source": "three Gaussian blobs",
                             "file": "bench/configs/blobs-3.json", "reduced": [],
                             "why": "a points kind"})
    bench["workloads"].append({"name": CELL, "config": "blobs-3", "traffic": "kmeans-one",
                               "chips": 1, "why": "pooled k-means"})
    bench["end_to_end"].append({"name": "job_s.points", "unit": "s", "better": "lower",
                                "bound": 0.25, "source": "host_clock", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # nothing that was there was edited, BENCHMARK.json aside
    after = {p: p.read_bytes() for p in before if p.name != "BENCHMARK.json"}
    assert after == {p: b for p, b in before.items() if p.name != "BENCHMARK.json"}
    return root


def _run(root, control=None) -> dict:
    from bench import harness

    return harness.run_cell(CELL, 11, 0.5, False, t_start=time.perf_counter(),
                            require_tpu=False, root=root, control=control)


def test_a_points_kind_added_as_files_runs_correct(points_root):
    res = _run(points_root)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"failed_requests", "label_mismatch"}
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "job_s.points"}
    # the itemsets cell still finds its own kind and generator in that root
    itemsets = spec.load_cell("itemsets.oneshot", points_root)
    assert spec.kind(itemsets.config["kind"], points_root).CONTROLS == ("bf16",)


def test_a_points_kind_s_planted_fault_is_not_correct(points_root):
    res = _run(points_root, control="answer_altered")
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["label_mismatch"]["value"] >= 1


def test_an_unknown_fault_of_a_kind_is_refused(points_root):
    with pytest.raises(ValueError, match="half_batch"):
        _run(points_root, control="half_batch")
