"""What the program reports about itself while it runs: named spans on the
profiler's clock, and a process-wide count of XLA compiles.

* ``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: when a
  profiler trace is being taken (``jax.profiler.trace``), the span lands
  on the host plane of the same ``.xplane.pb`` as the device's operations,
  on the same clock, with ``meta`` as the event's stats; otherwise it
  costs about a microsecond.  Every span name starts with ``repro.``.
  Spans mark phases (or one site, where a per-site loop already runs),
  never single candidates or itemsets.  The names, and where each opens,
  are listed in ``docs/serving.md``.
* ``compiles()`` is a snapshot of the XLA compiles this process has made
  so far (JAX's ``/jax/core/compile/backend_compile_duration`` events,
  which a load from the persistent compile cache raises too): their count
  and summed seconds.  The service takes its difference over each
  execution group.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **meta):
    """A profiler span named ``name`` (``repro.<phase>``), with ``meta``
    recorded as its stats.  Use as a context manager; ``set_metadata`` on
    the returned object adds stats before the span closes."""
    return jax.profiler.TraceAnnotation(name, **meta)


@dataclass(frozen=True)
class Compiles:
    count: int = 0
    seconds: float = 0.0

    def __sub__(self, other: Compiles) -> Compiles:
        return Compiles(self.count - other.count, self.seconds - other.seconds)


_count = 0
_seconds = 0.0


def _on_duration(event: str, duration: float, **_kw) -> None:
    global _count, _seconds
    if event == BACKEND_COMPILE_EVENT:
        _count += 1
        _seconds += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> Compiles:
    """The compiles this process has made since ``repro.obs`` was first
    imported."""
    return Compiles(_count, _seconds)
