"""The comparison that decides ``correct``.

Once the window has closed, every answer that the window's requests got
is compared with the plain reference (``bench.reference``) over the
dataset.

Numbers compared, each with its limit (``LIMITS``; see PERF.md for the
readings they were set from): ``failed_requests``, ``itemset_gap``
(itemsets frequent in one of the answer and the reference only) and
``count_error`` (largest gap of a support count).  Exact: limit 0.

``control`` replaces the answers with a broken stand-in, for the runs
and tests that must see ``correct`` false: ``"bf16"`` is the reference
itself computed in bfloat16 (support counts held in bfloat16), which
breaks the exactness the configuration states.  ``planted`` breaks the
timed path underneath, for the tests of each fault a cell can have.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import numpy as np

from bench import reference

LIMITS = {
    "failed_requests": 0,
    "itemset_gap": 0,
    "count_error": 0,
}


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


def _host_answer(app: str, res) -> dict:
    if app in ("gfm", "fdm", "cd_apriori"):
        return {"frequent": {tuple(int(i) for i in k): int(v) for k, v in res.frequent.items()}}
    raise ValueError(f"no comparison for app {app!r}")


def answers(dep, records) -> list[Answer]:
    """Every answer of the window's completed requests, copied to the host."""
    svc, out = dep.service, []
    for rid, rec, req in records:
        if rid is None or not rec.ok:
            continue
        out.append(Answer(app=req.app, params=dict(svc.request(rid).params),
                          value=_host_answer(req.app, svc.result(rid))))
    return out


def _itemset_checks(rows: np.ndarray, ans: list[Answer], control: str | None) -> list[Check]:
    worst = {"itemset_gap": 0, "count_error": 0}
    ref = reference.ItemsetReference(rows)
    stand_in = reference.ItemsetReference(rows, "bfloat16") if control == "bf16" else None
    n = rows.shape[0]
    k_max = max(a.params["k"] for a in ans)
    ref.frequent(min(reference.min_count(a.params["minsup"], n) for a in ans), k_max)
    for a in ans:
        thr = reference.min_count(a.params["minsup"], n)
        want = ref.frequent(thr, a.params["k"])
        got = stand_in.frequent(thr, a.params["k"]) if stand_in is not None else a.value["frequent"]
        worst["itemset_gap"] = max(worst["itemset_gap"], len(want.keys() ^ got.keys()))
        gaps = [abs(got[i] - want[i]) for i in want.keys() & got.keys()]
        worst["count_error"] = max([worst["count_error"], *gaps])
    return [Check(name, v, LIMITS[name]) for name, v in worst.items()]


def compare(rows: np.ndarray, ans: list[Answer], *, failed: int = 0,
            control: str | None = None) -> list[Check]:
    """Every number compared, with its limit; ``rows`` is the dataset."""
    out = [Check("failed_requests", failed, LIMITS["failed_requests"])]
    if not ans:
        return out + [Check("compared_answers", 0, -1)]  # nothing came back
    return out + _itemset_checks(rows, ans, control)


# ---------------------------------------------------------------------------
# faults planted in the timed path (tests only)
# ---------------------------------------------------------------------------


def _patch(obj, name: str, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    return lambda: setattr(obj, name, orig)


@contextlib.contextmanager
def planted(fault: str | None):
    """Break the timed path underneath the service while the block runs.

    * ``answer_altered``: the support-count kernel adds one to a count;
    * ``half_batch``: support counts come from the first half of the
      transactions, doubled.
    """
    undo = []
    if fault is None or fault == "bf16":
        yield
        return
    from repro.kernels import ops

    if fault == "answer_altered":
        def count_plus_one(orig):
            def f(*a, **kw):
                out = orig(*a, **kw)
                if isinstance(out, tuple):
                    return (out[0].at[..., 0].add(1),) + tuple(out[1:])
                return out.at[..., 0].add(1)
            return f
        for e in ("support_count", "support_count_prune", "support_count_sites",
                  "support_count_prune_sites"):
            undo.append(_patch(ops, e, count_plus_one))

    elif fault == "half_batch":
        def half_rows(orig):
            def f(tx, *a, **kw):
                n = tx.shape[-2]
                kept = tx.at[..., n // 2:, :].set(0)
                out = orig(kept, *a, **kw)
                return (out[0] * 2,) + tuple(out[1:]) if isinstance(out, tuple) else out * 2
            return f
        for e in ("support_count", "support_count_prune", "support_count_sites",
                  "support_count_prune_sites"):
            undo.append(_patch(ops, e, half_rows))

    else:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        for u in reversed(undo):
            u()
