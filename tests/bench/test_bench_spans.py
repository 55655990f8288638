"""The reduction of the program's own spans (``bench/metrics/spans.py``)
and the readers of the span metrics, on hand-made traces; and the span
metrics of a traced tiny run of the itemsets cell."""

from __future__ import annotations

import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest
from benchutil import ROOT, run_tiny

from bench import spec, trace
from bench.metrics import spans

CELL = "itemsets.oneshot"
SHARES = {  # metric -> span it reads
    "split_share.itemsets": "repro.split",
    "join_share.itemsets": "repro.level.join",
    "stage_share.itemsets": "repro.level.stage",
    "fold_share.itemsets": "repro.level.fold",
    "sync_share.itemsets": "repro.sync",
    "engine_share.itemsets": "repro.engine",
}
READERS = (*SHARES, "compiles_per_job.itemsets")

# one chip busy at 1-3 us and 6-7 us; one host thread with the benchmark's
# window (0-10 us) and step (0.2-9.8 us), and the program's spans nested in
# the step (times in us):
#   repro.step 0.3-9.7 > repro.request 0.4-9.6 (2 requests, 3 compiles)
#     > repro.split 0.45-0.9, repro.engine 1-9
#       > repro.job 1-3.5 > repro.level.count 1-3
#       > repro.job 3.6-8 > repro.level.join 3.6-4.6, repro.level.fold 4.6-5.9
#   repro.sync 9.9-11, past the window's end
US = 1_000_000  # picoseconds
HOST = [
    (1, "bench.window", 0, 10), (2, "bench.step", 0.2, 9.8),
    (3, "repro.step", 0.3, 9.7), (4, "repro.request", 0.4, 9.6),
    (5, "repro.split", 0.45, 0.9), (6, "repro.engine", 1.0, 9.0),
    (7, "repro.job", 1.0, 3.5), (8, "repro.level.count", 1.0, 3.0),
    (7, "repro.job", 3.6, 8.0), (9, "repro.level.join", 3.6, 4.6),
    (10, "repro.level.fold", 4.6, 5.9), (11, "repro.sync", 9.9, 11.0),
]
REQUEST_STATS = ('stats { metadata_id: 1 str_value: "4 5" } '
                 'stats { metadata_id: 2 int64_value: 3 }')


def xspace(host=HOST) -> str:
    events, meta = [], {}
    for mid, name, s, e in host:
        stats = REQUEST_STATS if name == "repro.request" else ""
        events.append(f"events {{ metadata_id: {mid} offset_ps: {round(s * US)} "
                      f"duration_ps: {round((e - s) * US)} {stats} }}")
        meta[mid] = name
    metadata = "\n".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
                         for k, v in meta.items())
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "support_count_pallas.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "copy.1" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 3 name: "python" timestamp_ns: 0
    {chr(10).join(events)}
  }}
  {metadata}
  stat_metadata {{ key: 1 value {{ id: 1 name: "request_ids" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "compiles" }} }}
}}
"""


def write(path: Path, text: str) -> Path:
    from jax.profiler import ProfileData

    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


BENCH_ONLY = [h for h in HOST if h[1].startswith("bench.")]


def test_self_time_per_span_name_clipped_to_the_window(tmp_path):
    got = spans.reduce(write(tmp_path / "t.xplane.pb", xspace()))
    assert got.window_s == pytest.approx(10e-6)
    want = {  # [count, self us]
        "repro.step": (1, 0.2), "repro.request": (1, 0.75), "repro.split": (1, 0.45),
        "repro.engine": (1, 1.1), "repro.job": (2, 2.6), "repro.level.count": (1, 2.0),
        "repro.level.join": (1, 1.0), "repro.level.fold": (1, 1.3),
        "repro.sync": (1, 0.1),  # 9.9-11 us, clipped at the window's end
    }
    assert set(got.spans) == set(want)
    for name, (n, us) in want.items():
        assert got.spans[name][0] == n, name
        assert got.spans[name][1] == pytest.approx(us * 1e-6), name
    # the self times and the time outside any program span fill the window
    outside = 0.3 + 0.2  # before repro.step, and from its end to repro.sync
    assert sum(s for _, s in got.spans.values()) == pytest.approx((10 - outside) * 1e-6)
    assert got.share("repro.level.join") == pytest.approx(10.0)
    assert got.share("repro.level.stage") == 0.0
    assert got.request_compiles == [(3, 2)]


def test_idle_gaps_are_named_by_the_innermost_program_span(tmp_path):
    got = trace.reduce(write(tmp_path / "t.xplane.pb", xspace()))
    gaps = sorted((round(secs * 1e9), name) for secs, name in got.gaps)
    # 0-1 us (midpoint in the split), 3-6 us (in the join, which opens
    # with its job), 7-10 us (in the engine, after the second job)
    assert gaps == [(1000, "repro.split"), (3000, "repro.engine"), (3000, "repro.level.join")]


def test_program_spans_leave_the_benchmark_reduction_as_it_was(tmp_path):
    with_program = trace.reduce(write(tmp_path / "a.xplane.pb", xspace()))
    bench_only = trace.reduce(write(tmp_path / "b.xplane.pb", xspace(BENCH_ONLY)))
    assert with_program.window_s == bench_only.window_s
    assert with_program.busy_s == bench_only.busy_s
    assert with_program.ops == bench_only.ops
    assert with_program.breakdown()["device_ops"] == bench_only.breakdown()["device_ops"]
    assert [name for _, name in bench_only.gaps] == ["bench.step"] * 3


def test_a_trace_without_program_spans_has_none(tmp_path):
    assert spans.reduce(write(tmp_path / "b.xplane.pb", xspace(BENCH_ONLY))) is None


def _readers_over(tmp_path: Path, host) -> dict:
    """Each span metric's reading of a run whose traced window holds
    ``host``, from reader files in a checkout at ``tmp_path``."""
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    for name in READERS:
        shutil.copy(ROOT / "bench" / "metrics" / f"{name}.py", tmp_path / "bench" / "metrics")
    write(tmp_path / ".bench_trace" / "cell" / "host.xplane.pb", xspace(host))
    ctx = SimpleNamespace(trace=object(), cell=SimpleNamespace(name="cell"))
    return {name: spec.metric_reader(name, tmp_path)(ctx) for name in READERS}


def test_readers_report_self_time_shares_and_compiles(tmp_path):
    got = _readers_over(tmp_path, HOST)
    want = {"split_share.itemsets": 4.5, "join_share.itemsets": 10.0,
            "stage_share.itemsets": 0.0,  # the phase never ran: 0, not nothing
            "fold_share.itemsets": 13.0, "sync_share.itemsets": 1.0,
            "engine_share.itemsets": 11.0}
    for name, share in want.items():
        assert got[name]["value"] == pytest.approx(share), name
    assert got["stage_share.itemsets"]["spans"] == 0
    assert got["compiles_per_job.itemsets"] == {"value": 3.0, "requests": 2}


def test_readers_read_nothing_without_program_spans(tmp_path):
    assert _readers_over(tmp_path, BENCH_ONLY) == dict.fromkeys(READERS)


def test_readers_read_nothing_of_an_untraced_run(tmp_path):
    ctx = SimpleNamespace(trace=None, cell=SimpleNamespace(name="cell"))
    assert all(spec.metric_reader(name)(ctx) is None for name in READERS)


def test_traced_run_reports_the_span_metrics():
    res = run_tiny(CELL, trace=True)
    assert res["correct"] is True, res["checks"]
    metrics = res["metrics"]
    assert set(READERS) <= set(metrics)
    for name in SHARES:
        assert 0 <= metrics[name]["value"] <= 100, (name, metrics[name])
    assert sum(metrics[name]["value"] for name in SHARES) <= 100
    assert metrics["join_share.itemsets"]["value"] > 0
    assert metrics["compiles_per_job.itemsets"]["value"] == 0  # warm-up compiled it all
