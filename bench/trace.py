"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports: busy time (the union of the intervals in which an
operation ran on a chip, clipped to the window), the traced window, each device operation's
time, and the longest idle gaps named by the benchmark's host span open
at the time.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events of the line ``XLA Ops``, named by HLO instruction.  Host spans
are the benchmark's own ``TraceAnnotation`` events (names starting
``bench.``) on the host plane.
Times within one trace share a clock, in nanoseconds.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """The HLO instruction's own name: TPU traces name an op event by its
    whole HLO text (``%support_count_pallas.1 = s32[...] custom-call(...)``),
    in which operands name other instructions."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def start(log_dir: Path) -> None:
    """Start the profiler with host tracing on and Python tracing off."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def union_length(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


@dataclass
class Summary:
    window_s: float
    busy_s: float  # mean over the chips that ran anything
    ops: dict = field(default_factory=dict)  # op name -> [count, seconds]
    gaps: list = field(default_factory=list)  # (seconds, host span) longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, pattern: str) -> tuple[int, float]:
        """(events, seconds) of the device ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        n, s = 0, 0.0
        for name, (cnt, secs) in self.ops.items():
            if rx.search(name):
                n, s = n + cnt, s + secs
        return n, s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        return {"device_ops": [[name, secs] for name, (_, secs) in ops],
                "idle_gaps": [[name, secs] for secs, name in self.gaps[:top]]}


def _xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce(path: Path) -> Summary:
    """The summary of the trace at ``path`` (a file, or a directory the
    profiler wrote)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(_xplane(Path(path))))
    chips: list[list] = []  # per chip, its ops' (start, end, name)
    spans: list = []
    t_lo, t_hi = None, None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = [(ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                    for line in plane.lines if line.name == OPS_LINE for ev in line.events]
            if chip:
                chips.append(chip)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
                        if ev.name == "bench.window":
                            t_lo, t_hi = ev.start_ns, ev.start_ns + ev.duration_ns
    if t_lo is None:  # no window span: the extent of everything recorded
        edges = [t for chip in chips for s, e, _ in chip for t in (s, e)]
        edges += [t for s, e, _ in spans for t in (s, e)]
        t_lo, t_hi = min(edges), max(edges)
    window_s = (t_hi - t_lo) / 1e9
    # every op's time inside the window only
    busy_by_chip: list[float] = []
    ops: dict = defaultdict(lambda: [0, 0.0])
    intervals: list = []
    for chip in chips:
        inside = [(max(s, t_lo), min(e, t_hi), name) for s, e, name in chip
                  if e > t_lo and s < t_hi]
        for s, e, name in inside:
            ops[name][0] += 1
            ops[name][1] += (e - s) / 1e9
        busy, merged = union_length([(s, e) for s, e, _ in inside])
        busy_by_chip.append(busy / 1e9)
        intervals.extend(merged)
    busy_s = sum(busy_by_chip) / len(busy_by_chip) if busy_by_chip else 0.0
    # idle gaps: when no chip ran anything, inside the window
    _, merged = union_length(intervals)
    edges = [t_lo] + [t for iv in merged for t in iv] + [t_hi]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            inner = [sp for sp in spans if sp[0] <= mid <= sp[1] and sp[2] != "bench.window"]
            name = max(inner, key=lambda sp: sp[0])[2] if inner else "no bench span"
            gaps.append(((b - a) / 1e9, name))
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_s=window_s, busy_s=busy_s, ops=dict(ops), gaps=gaps)
