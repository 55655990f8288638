"""The comparison that decides ``correct``.

Once the window has closed, every answer that the window's requests got
is compared with the plain reference of the dataset's kind
(``bench/kinds/<kind>.py``) over the dataset.

Numbers compared, each with its limit: ``failed_requests`` (limit 0),
``compared_answers`` when no answer came back at all (always fails), and
the kind's own (its ``LIMITS``; see PERF.md for the readings they were
set from).

``control`` replaces the answers with a broken stand-in that the kind
computes (one of its ``CONTROLS``), for the runs and tests that must see
``correct`` false.  ``planted`` breaks the timed path underneath with one
of the kind's ``FAULTS``, for the tests of each fault a cell can have.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import numpy as np

FAILED_LIMIT = 0


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Answer:
    app: str
    params: dict
    value: dict  # the answer, on the host


def answers(dep, records) -> list[Answer]:
    """Every answer of the window's completed requests, copied to the host
    by the deployment's kind."""
    svc, out = dep.service, []
    for rid, rec, req in records:
        if rid is None or not rec.ok:
            continue
        out.append(Answer(app=req.app, params=dict(svc.request(rid).params),
                          value=dep.kind.host_answer(req.app, svc.result(rid))))
    return out


def compare(kind, rows: np.ndarray, ans: list[Answer], *, failed: int = 0,
            control: str | None = None) -> list[Check]:
    """Every number compared, with its limit; ``rows`` is the dataset and
    ``kind`` its kind's module."""
    out = [Check("failed_requests", failed, FAILED_LIMIT)]
    if not ans:
        return out + [Check("compared_answers", 0, -1)]  # nothing came back
    return out + kind.compare(rows, ans, control)


# ---------------------------------------------------------------------------
# faults planted in the timed path (tests only)
# ---------------------------------------------------------------------------


def patch(obj, name: str, make):
    """Replace ``obj.name`` by ``make(original)``; returns the undo."""
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    return lambda: setattr(obj, name, orig)


@contextlib.contextmanager
def planted(fault: str | None, kind):
    """Break the timed path underneath the service with the kind's fault
    ``fault`` while the block runs; a control, or ``None``, plants
    nothing."""
    if fault is None or fault in kind.CONTROLS:
        yield
        return
    if fault not in kind.FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {sorted(kind.FAULTS)})")
    undo = kind.FAULTS[fault]()
    try:
        yield
    finally:
        for u in reversed(undo):
            u()
