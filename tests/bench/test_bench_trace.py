"""``bench/trace.py``'s reduction from a profiler trace to busy time,
idle gaps and kernel time."""

from __future__ import annotations

from pathlib import Path

import pytest
from benchutil import ROOT  # noqa: F401  puts the checkout on the path

from bench import trace

# one chip and one host thread, times in picoseconds from each line's start
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%support_count_prune_pallas.1 = (s32[4,1,512]) custom-call(s32[4,3,512] %pad.5)" } }
  event_metadata { key: 2 value { id: 2 name: "%slice.2 = s32[4,1,64] slice(s32[4,1,512] %support_count_prune_pallas.1)" } }
  event_metadata { key: 3 value { id: 3 name: "kmeans_assign_pallas.9" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 200000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture
def trace_file(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return path


def test_union_of_intervals():
    total, merged = trace.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [(0, 3), (5, 8)]


def test_busy_window_and_idle_share(trace_file):
    s = trace.reduce(trace_file)
    assert s.window_s == pytest.approx(10e-6)  # the bench.window span
    assert s.busy_s == pytest.approx(3e-6)  # 1-3 us and 6-7 us; the overlap counts once
    assert s.idle_share == pytest.approx(0.7)


def test_busy_time_is_clipped_to_the_window(tmp_path):
    from jax.profiler import ProfileData

    # the kmeans op now runs 6-12 us, past the window's end at 10 us
    late = XSPACE.replace("offset_ps: 5000000 duration_ps: 1000000",
                          "offset_ps: 5000000 duration_ps: 6000000")
    path = tmp_path / "late.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(late))
    s = trace.reduce(path)
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(6e-6)  # 1-3 us and 6-10 us
    assert s.kernel(r"kmeans_assign") == (1, pytest.approx(4e-6))


def test_kernel_time_by_name(trace_file):
    s = trace.reduce(trace_file)
    # the slice that reads the kernel's output is not the kernel
    assert s.kernel(r"support_count") == (1, pytest.approx(2e-6))
    assert s.kernel(r"kmeans_assign") == (1, pytest.approx(1e-6))
    assert s.kernel(r"no_such_kernel") == (0, 0.0)


def test_idle_gaps_are_named_by_the_open_bench_span(trace_file):
    b = trace.reduce(trace_file).breakdown()
    assert [name for name, _ in b["device_ops"]] == [
        "support_count_prune_pallas.1", "kmeans_assign_pallas.9", "slice.2"]
    gaps = sorted((round(secs * 1e9), name) for name, secs in b["idle_gaps"])
    # 0-1 us before the first op, 3-6 us with its midpoint in the step,
    # 7-10 us after the last op
    assert gaps == [(1000, "no bench span"), (3000, "bench.step"), (3000, "no bench span")]


def test_a_trace_recorded_on_one_v5e():
    # two rounds of support_count_sites and kmeans_assign_sites at tiny
    # shapes, a 10 ms host sleep after each count, under a bench.tiny span
    s = trace.reduce(Path(__file__).with_name("tiny_v5e.xplane.pb"))
    assert 0 < s.busy_s < s.window_s
    assert s.kernel(r"support_count") == (2, pytest.approx(3.068e-05))
    assert s.kernel(r"kmeans_assign") == (2, pytest.approx(3.9621e-05))
    gaps = s.breakdown()["idle_gaps"]
    assert gaps[0][0] == "bench.tiny" and 0.010 < gaps[0][1] < 0.013  # the sleeps
    assert gaps[1][0] == "bench.tiny" and 0.010 < gaps[1][1] < 0.013


def test_program_span_names_the_gaps_of_the_v5e_trace(tmp_path):
    from jax.profiler import ProfileData

    # the recorded trace's device ops and its bench.tiny span, rewritten
    # with a program span open inside bench.tiny from 1 ns after it opens
    # to 1 ns before it closes
    pd = ProfileData.from_file(str(Path(__file__).with_name("tiny_v5e.xplane.pb")))
    ops, host = [], []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name == "/device:TPU:0" and line.name == trace.OPS_LINE:
                ops = [(ev.start_ns, ev.duration_ns, trace.op_name(ev.name))
                       for ev in line.events]
            host += [(ev.start_ns, ev.duration_ns, ev.name) for ev in line.events
                     if ev.name == "bench.tiny"]
    (t0, dur, _), = host
    host.append((t0 + 1, dur - 2, "repro.level.count"))

    def plane(pid, name, line, events):
        names = sorted({n for _, _, n in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "\n".join(f"events {{ metadata_id: {ids[n]} offset_ps: {round(s * 1000)} "
                        f"duration_ps: {round(d * 1000)} }}" for s, d, n in events)
        meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                         for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: {pid} name: "{line}" '
                f"timestamp_ns: 0 {evs} }} {meta} }}")

    path = tmp_path / "v5e_program.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        plane(1, "/device:TPU:0", trace.OPS_LINE, ops) + plane(2, "/host:CPU", "python", host)))
    s, base = trace.reduce(path), trace.reduce(Path(__file__).with_name("tiny_v5e.xplane.pb"))
    assert s.window_s == pytest.approx(base.window_s) and s.busy_s == pytest.approx(base.busy_s)
    assert s.kernel(r"support_count") == (2, pytest.approx(3.068e-05))
    gaps = s.breakdown()["idle_gaps"]
    assert {name for _, name in s.gaps} == {"repro.level.count"}
    assert 0.010 < gaps[0][1] < 0.013 and 0.010 < gaps[1][1] < 0.013  # the sleeps
    assert [round(secs, 9) for _, secs in gaps] == [round(secs, 9) for _, secs in
                                                    base.breakdown()["idle_gaps"]]
