"""The benchmark's arithmetic and its data-driven lookups, on the CPU."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest
from benchutil import ROOT

from bench import spec, stats, traffic
from bench.metrics import work


def test_refuses_a_host_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "itemsets.oneshot", "--seed", "0",
         "--seconds", "10", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "itemsets.oneshot", "--seed", "0",
         "--seconds", "10", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("mix", sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_traffic_is_a_function_of_the_seed(mix):
    m = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    assert traffic.rounds(m, 2**31 + 11) == traffic.rounds(m, 2**31 + 11)
    assert traffic.rounds(m, 5) != traffic.rounds(m, 6)
    # every seed gets the same apps and fixed parameters, in the same order
    strip = [[[(r.app, {k: v for k, v in r.params.items() if k not in ("seed", "split_seed")})
               for r in rnd] for rnd in c] for c in traffic.rounds(m, 5)]
    assert strip == [[[(r.app, {k: v for k, v in r.params.items()
                                if k not in ("seed", "split_seed")}) for r in rnd]
                      for rnd in c] for c in traffic.rounds(m, 6)]


def test_fresh_parameters_never_repeat():
    m = json.loads((ROOT / "bench" / "traffic" / "oneshot-miners.json").read_text())
    m = {**m, "clients": 3, "tenants": ["a", "b", "c"], "rounds": 5}
    seen = [r.params["split_seed"] for c in traffic.rounds(m, 1) for rnd in c for r in rnd]
    assert len(seen) == 3 * 5 * 3 and len(set(seen)) == len(seen)
    assert all(0 <= s < 2**31 for s in seen)


def test_end_to_end_metrics_take_their_quantity_s_arithmetic():
    from bench import harness

    cell = spec.load_cell("itemsets.oneshot")
    done = [stats.Done("gfm", 0.0, 2.0, True), stats.Done("fdm", 2.0, 6.0, True)]
    got = harness.end_to_end(cell, done, setup_s=12.5)
    assert got == {"setup_s": {"value": 12.5, "unit": "s"},
                   "job_s.itemsets": {"value": 3.0, "unit": "s"}}
    bad = spec.Cell(**{**cell.__dict__, "end_to_end": ({"name": "p99_s", "unit": "s"},)})
    with pytest.raises(spec.SpecError, match="p99_s"):
        harness.end_to_end(bad, done, setup_s=1.0)


def test_job_s_is_the_sum_over_the_count():
    done = [stats.Done("gfm", 0.0, 2.0, True), stats.Done("fdm", 1.0, 5.0, True),
            stats.Done("cd_apriori", 5.0, 5.5, True)]
    assert stats.job_s(done) == pytest.approx((2.0 + 4.0 + 0.5) / 3)
    with pytest.raises(ValueError):
        stats.job_s([])


def test_support_count_work():
    ops, nbytes = work.support_count(n_tx=1000, n_cand=10, n_items=96, words=3)
    assert ops == 2 * 1000 * 10 * 96
    assert nbytes == 4 * (1000 * 3 + 10 * 3 + 10)


def test_roofline_names_its_bound():
    r = work.roofline(ops=2e12, nbytes=1e9, seconds=1.0, peak_ops=1e12, peak_bytes_per_s=1e12)
    assert r["bound"] == "compute" and r["value"] == pytest.approx(200.0)
    r = work.roofline(ops=1e9, nbytes=819e9, seconds=2.0, peak_ops=1e12, peak_bytes_per_s=819e9)
    assert r["bound"] == "memory" and r["value"] == pytest.approx(50.0)


def test_peaks_of_a_v5e_and_an_unknown_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="TPU v9"):
        spec.peaks("TPU v9")


def test_every_cell_resolves_by_name():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((root / "bench/configs/quest-t10i4-n1000-paper200.json").read_text())
    cfg.update(name="quest-t40i10d100k")
    cfg["data"].update(avg_tx_len=40, avg_pattern_len=10, n_tx=100000)
    (root / "bench/configs/quest-t40i10d100k.json").write_text(json.dumps(cfg))
    mix = {"clients": 2, "max_requests": 4, "rounds": 2,
           "apps": [{"app": "fdm", "params": {"k": 3, "minsup": 0.01}, "fresh": ["split_seed"]}]}
    (root / "bench/traffic/fdm-pair.json").write_text(json.dumps(mix))
    (root / "bench/metrics/miner.p50_s.py").write_text("def read(ctx):\n    return 1.5\n")
    bench["configs"].append({"name": "quest-t40i10d100k", "source": "FIMI T40I10D100K",
                             "file": "bench/configs/quest-t40i10d100k.json",
                             "reduced": [], "why": "longer baskets"})
    bench["workloads"].append({"name": "fdm.pair", "config": "quest-t40i10d100k",
                               "traffic": "fdm-pair", "chips": 1, "why": "two clients"})
    bench["per_layer"].append({"name": "miner.p50_s", "unit": "s", "better": "lower",
                               "source": "host_clock", "layer": "mining algorithms",
                               "moves": "job_s.fdm", "workloads": ["fdm.pair"]})
    bench["end_to_end"].append({"name": "job_s.fdm", "unit": "s", "better": "lower",
                                "bound": 0.05, "source": "host_clock", "workloads": ["fdm.pair"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p.relative_to(root): p.read_bytes() for p in (root / "bench").rglob("*")
             if p.is_file() and p.relative_to(root) in before}
    assert after == before  # nothing that was there was edited
    cell = spec.load_cell("fdm.pair", root)
    assert cell.config["data"]["avg_tx_len"] == 40 and cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["miner.p50_s"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "job_s.fdm"]
    assert spec.metric_reader("miner.p50_s", root)(None) == 1.5
    assert len(traffic.rounds(cell.traffic, 1)[1]) == 2
    assert spec.load_cell("itemsets.oneshot", root).config["data"]["avg_tx_len"] == 10


class _FakeService:
    """Completes every queued request at the next step; counts how often
    its stored results were dropped."""

    def __init__(self):
        from repro.runtime.cache import ResultCache

        self._cache, self.forgot, self.queued, self.sent = ResultCache(4), -1, [], []
        self.cache = self._cache

    @property
    def cache(self):
        return self._cache

    @cache.setter
    def cache(self, value):
        self._cache, self.forgot = value, self.forgot + 1

    def submit(self, tenant, app, dataset, params):
        self.sent.append((tenant, app, params["split_seed"]))
        self.queued.append(len(self.sent))
        return len(self.sent)

    def step(self, max_requests):
        done, self.queued = self.queued, []
        return done

    drain = step

    def poll(self, rid):
        return "done"


@pytest.mark.parametrize("clients", [1, 2])
def test_the_window_replays_the_warm_up_in_whole_rounds(clients):
    from types import SimpleNamespace

    from bench import harness

    m = json.loads((ROOT / "bench" / "traffic" / "oneshot-miners.json").read_text())
    m = {**m, "clients": clients, "tenants": ["a", "b"][:clients], "rounds": 2}
    plan = traffic.rounds(m, 7)
    warm, window = _FakeService(), _FakeService()
    assert harness._warm_up(SimpleNamespace(service=warm, dataset="tx"), m, plan) == 6 * clients
    records, _ = harness._window(SimpleNamespace(service=window, dataset="tx"), m, plan, 0.0)
    # a window closed at once still runs each client's rounds whole, with
    # the warm-up's own requests, and no stored result survives into a round
    assert len(records) == 6 * clients and all(rec.ok for _, rec, _ in records)
    assert sorted(window.sent) == sorted(warm.sent)
    assert warm.forgot == 2 and window.forgot == 2 * clients


def test_the_recorder_counts_each_call_s_non_empty_candidates():
    import jax.numpy as jnp
    import numpy as np

    from bench.kernels import KernelRecorder
    from repro.kernels import ops

    before = ops.support_count_sites
    tx = jnp.asarray(np.arange(16, dtype=np.uint32).reshape(2, 8, 1))
    masks = np.zeros((2, 4, 1), np.uint32)
    masks[0, :2, 0] = [1, 3]
    masks[1, 0, 0] = 2
    with KernelRecorder(spec.kind("transactions").KERNELS) as rec:
        ops.support_count_sites(tx, jnp.asarray(masks))
    assert ops.support_count_sites is before
    (call,) = rec.resolve()
    assert (call.entry, call.sites, call.n_tx, call.words) == ("support_count_sites", 2, 8, 1)
    assert call.n_cand_total == 3 and call.masks is None


def test_support_count_roofline_reads_calls_over_kernel_time():
    from types import SimpleNamespace

    Call = spec.kind("transactions").Call
    read = spec.metric_reader("support_count_roofline")
    cell = spec.load_cell("itemsets.oneshot")
    calls = [Call("support_count_prune_sites", sites=200, n_tx=2500, words=32, masks=None,
                  n_cand_total=200 * 4000)]
    trace = SimpleNamespace(kernel=lambda pattern: (1, 0.25))
    peaks = spec.peaks("TPU v5 lite")
    got = read(SimpleNamespace(cell=cell, kernel_calls=calls, trace=trace, peaks=peaks))
    ops = 2.0 * 200 * 2500 * 4000 * 1000
    assert got["value"] == pytest.approx(100 * ops / 393e12 / 0.25)
    assert got["bound"] == "compute" and got["calls"] == 1 and got["events"] == 1
    assert read(SimpleNamespace(cell=cell, kernel_calls=[], trace=trace, peaks=peaks)) is None
