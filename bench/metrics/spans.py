"""The program's own spans in a traced run: for each span name that starts
``repro.``, its count and self seconds inside the traced window, and the
compiles recorded on each request span.  (The device's idle gaps, named
by the innermost of these spans open at their midpoints, are
``bench.trace``'s.)

The program opens these spans itself (``repro.obs.span``) on the host
plane of the same profile as the device's operations, on the same clock.
Self seconds are a span's time inside the window less the part covered
by the program spans nested in it on the same host line.

The span metrics' readers share one reduction of the profile that the
harness writes for the window, under ``.bench_trace/<cell>`` at the root
of the checkout the reader is in.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

from bench.trace import _xplane

PREFIX = "repro."
WINDOW = "bench.window"
REQUEST = "repro.request"


@dataclass
class Spans:
    window_s: float
    spans: dict = field(default_factory=dict)  # name -> [count, self seconds]
    # (compiles, requests) of each request span that starts in the window
    request_compiles: list = field(default_factory=list)

    def share(self, name: str) -> float:
        """``name``'s self time as a % of the window; 0 if it never ran."""
        return 100.0 * self.spans.get(name, (0, 0.0))[1] / self.window_s


def self_times(events: list, t_lo: float, t_hi: float) -> dict:
    """name -> [count, self seconds] of one host line's spans, each
    clipped to the window [t_lo, t_hi] (ns); ``events`` are (start, end,
    name) tuples that nest, as the spans of one thread do.  A span is
    counted if any of it lies inside the window."""
    out: dict = defaultdict(lambda: [0, 0.0])
    open_: list = []  # [end, name, clipped length, children's clipped length]

    def close(entry):
        out[entry[1]][1] += (entry[2] - entry[3]) / 1e9

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while open_ and open_[-1][0] <= s:
            close(open_.pop())
        length = max(0.0, min(e, t_hi) - max(s, t_lo))
        if open_:
            open_[-1][3] += length
        open_.append([e, name, length, 0.0])
        if e > t_lo and s < t_hi:
            out[name][0] += 1
    for entry in open_:
        close(entry)
    return {k: v for k, v in out.items() if v[0]}


def reduce(path: Path) -> Spans | None:
    """The program spans of the profile at ``path`` (a file, or the
    directory the profiler wrote); ``None`` when it holds none."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(_xplane(Path(path))))
    lines: list[list] = []  # per host line, its program spans
    requests: list = []  # (start, compiles, number of request ids)
    t_lo = t_hi = None
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                mine = []
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == WINDOW:
                        t_lo, t_hi = s, e
                    elif ev.name.startswith(PREFIX):
                        mine.append((s, e, ev.name))
                        if ev.name == REQUEST:
                            meta = dict(ev.stats)
                            ids = str(meta.get("request_ids", "")).split()
                            requests.append((s, int(meta.get("compiles", 0)), len(ids)))
                if mine:
                    lines.append(mine)
    if not lines:
        return None
    if t_lo is None:  # no window span: the extent of the program's spans
        t_lo = min(s for line in lines for s, _, _ in line)
        t_hi = max(e for line in lines for _, e, _ in line)
    spans: dict = defaultdict(lambda: [0, 0.0])
    for line in lines:
        for name, (n, secs) in self_times(line, t_lo, t_hi).items():
            spans[name][0] += n
            spans[name][1] += secs
    return Spans(window_s=(t_hi - t_lo) / 1e9, spans=dict(spans),
                 request_compiles=[(c, n) for s, c, n in requests if t_lo <= s < t_hi])


_cache: dict = {}


def of_run(ctx, reader_file: str) -> Spans | None:
    """The program spans of the traced window of ``ctx``'s run, or
    ``None`` when the run was not traced or the program opened none."""
    if ctx.trace is None:
        return None
    trace_dir = Path(reader_file).resolve().parents[2] / ".bench_trace" / ctx.cell.name
    try:
        found = _xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = (str(found), found.stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce(found)
    return _cache[key]


def share(ctx, reader_file: str, name: str):
    """The reading of a span metric: ``name``'s self time as a % of the
    traced window, with its count and seconds."""
    got = of_run(ctx, reader_file)
    if got is None:
        return None
    n, secs = got.spans.get(name, (0, 0.0))
    return {"value": got.share(name), "self_s": secs, "spans": n}
