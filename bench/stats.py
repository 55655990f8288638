"""End-to-end metric arithmetic over the requests of one measured window.

A request is a ``Done`` record: when it was submitted and when its
client saw the result, on the host clock, and whether it succeeded.  A
request that failed or was refused has no result time; it counts as
missing, with infinite latency.
"""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass
class Done:
    app: str
    submit_t: float
    done_t: float | None  # None: failed or refused
    ok: bool

    @property
    def latency_s(self) -> float:
        return self.done_t - self.submit_t if self.ok and self.done_t is not None else math.inf


def job_s(done: list[Done]) -> float:
    """Mean time to result: the sum of the jobs' latencies over their
    count (a failed job makes it infinite)."""
    if not done:
        raise ValueError("no job completed in the window")
    return sum(d.latency_s for d in done) / len(done)
