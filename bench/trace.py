"""Reduce a profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports: busy time (the union of the intervals in which an
operation ran on a chip, clipped to the window), the traced window, each
device operation's time, and the longest idle gaps, each named by the
innermost host span open at its midpoint.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events of the line ``XLA Ops``, named by HLO instruction.  Host spans
are the ``TraceAnnotation`` events on the host plane of the benchmark
(names starting ``bench.``) and of the program (``repro.``).
Times within one trace share a clock, in nanoseconds.
"""

from __future__ import annotations

import heapq
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
WINDOW = "bench.window"


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


def idle_gaps(merged: list[tuple[float, float]], t_lo: float, t_hi: float, spans: list,
              nothing: str) -> list[tuple[float, str]]:
    """(seconds, name) of each gap between the merged busy intervals
    inside [t_lo, t_hi], longest first.  A gap is named by the innermost
    of ``spans`` ((start, end, name) tuples) open at its midpoint: the
    latest to open, and of those the first to close; ``nothing`` where
    none is open."""
    edges = [t_lo] + [t for iv in merged for t in iv] + [t_hi]
    mids = sorted(((a + b) / 2, a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a)
    by_start = sorted(spans)
    open_: list = []  # heap of (-start, end, name); ended spans leave lazily
    gaps, i = [], 0
    for mid, a, b in mids:
        while i < len(by_start) and by_start[i][0] <= mid:
            s, e, name = by_start[i]
            heapq.heappush(open_, (-s, e, name))
            i += 1
        while open_ and open_[0][1] < mid:  # midpoints rise: an ended span stays ended
            heapq.heappop(open_)
        gaps.append(((b - a) / 1e9, open_[0][2] if open_ else nothing))
    gaps.sort(key=lambda g: -g[0])
    return gaps


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
    spans: list = []  # the benchmark's
    program: list = []  # the program's
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
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        t_lo, t_hi = s, e
                    elif ev.name.startswith(SPAN_PREFIX):
                        spans.append((s, e, ev.name))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append((s, e, ev.name))
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
    gaps = idle_gaps(merged, t_lo, t_hi, spans + program, "no bench span")
    return Summary(window_s=window_s, busy_s=busy_s, ops=dict(ops), gaps=gaps)
