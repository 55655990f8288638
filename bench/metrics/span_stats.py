"""The stats the program records on its spans (``repro.obs.span(name,
**meta)``), summed over the spans of one name that start inside the
traced window, for the readers of program counters.  (Self times are
``bench.metrics.spans``'.)

The profile is read once per traced run and shared by every reader.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from pathlib import Path

from bench.metrics.spans import PREFIX, WINDOW
from bench.trace import _xplane

_cache: dict = {}


def _sums(path: Path) -> dict:
    """name -> (spans, stat -> sum) of every program span of the profile
    at ``path`` that starts inside its window (the whole profile when it
    has no window span)."""
    from jax.profiler import ProfileData

    window, found = None, []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(PREFIX):
                        found.append((ev.start_ns, ev.name, dict(ev.stats)))
    out: dict = defaultdict(lambda: [0, Counter()])
    for start, name, stats in found:
        if window is None or window[0] <= start < window[1]:
            out[name][0] += 1
            out[name][1].update({k: int(v) for k, v in stats.items()
                                 if isinstance(v, (int, float))})
    return {name: (n, sums) for name, (n, sums) in out.items()}


def totals(ctx, reader_file: str, name: str) -> tuple[int, Counter] | None:
    """(spans, stat -> sum) of the spans named ``name`` in the traced
    window of ``ctx``'s run; ``None`` when the run was not traced or
    holds no such span."""
    if ctx.trace is None:
        return None
    trace_dir = Path(reader_file).resolve().parents[2] / ".bench_trace" / ctx.cell.name
    try:
        path = _xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        _cache[key] = _sums(path)
    return _cache[key].get(name)
