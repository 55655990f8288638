"""The ``transactions`` dataset kind: a basket table mined for frequent
itemsets by the grid miners (``gfm``, ``fdm``, ``cd_apriori``).

Numbers compared (``LIMITS``; PERF.md gives the readings they were set
from): ``itemset_gap`` (itemsets frequent in one of the answer and the
reference only) and ``count_error`` (largest gap of a support count).
Exact: limit 0.  The control ``bf16`` is the reference itself with its
support counts held in bfloat16 (``bench.reference``), which breaks the
exactness the configuration states.  The faults: ``answer_altered`` (the
support-count kernel adds one to a count) and ``half_batch`` (counts
come from the first half of the transactions, doubled).  The kernel
recorder takes the support count's four entry points, with each call's
sites, rows and words, and its candidate masks by reference until the
window has closed.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench import reference
from bench.checks import Check, patch

APPS = ("gfm", "fdm", "cd_apriori")

LIMITS = {
    "itemset_gap": 0,
    "count_error": 0,
}

CONTROLS = ("bf16",)


def load(svc, dataset: str, data: dict, rows: np.ndarray) -> None:
    svc.register_dataset(dataset, "transactions", n_items=data["n_items"])
    svc.append_transactions(dataset, rows)


def host_answer(app: str, res) -> dict:
    if app in APPS:
        return {"frequent": {tuple(int(i) for i in k): int(v) for k, v in res.frequent.items()}}
    raise ValueError(f"no comparison for app {app!r}")


def compare(rows: np.ndarray, answers: list, control: str | None) -> list[Check]:
    """Each answer against Apriori over ``rows``, the dense table."""
    worst = {"itemset_gap": 0, "count_error": 0}
    ref = reference.ItemsetReference(rows)
    stand_in = reference.ItemsetReference(rows, "bfloat16") if control == "bf16" else None
    n = rows.shape[0]
    k_max = max(a.params["k"] for a in answers)
    ref.frequent(min(reference.min_count(a.params["minsup"], n) for a in answers), k_max)
    for a in answers:
        thr = reference.min_count(a.params["minsup"], n)
        want = ref.frequent(thr, a.params["k"])
        got = stand_in.frequent(thr, a.params["k"]) if stand_in is not None else a.value["frequent"]
        worst["itemset_gap"] = max(worst["itemset_gap"], len(want.keys() ^ got.keys()))
        gaps = [abs(got[i] - want[i]) for i in want.keys() & got.keys()]
        worst["count_error"] = max([worst["count_error"], *gaps])
    return [Check(name, v, LIMITS[name]) for name, v in worst.items()]


# ---------------------------------------------------------------------------
# faults planted in the timed path (tests only)
# ---------------------------------------------------------------------------

COUNT_ENTRIES = ("support_count", "support_count_prune", "support_count_sites",
                 "support_count_prune_sites")


def _count_plus_one(orig):
    def f(*a, **kw):
        out = orig(*a, **kw)
        if isinstance(out, tuple):
            return (out[0].at[..., 0].add(1),) + tuple(out[1:])
        return out.at[..., 0].add(1)
    return f


def _half_rows(orig):
    def f(tx, *a, **kw):
        n = tx.shape[-2]
        kept = tx.at[..., n // 2:, :].set(0)
        out = orig(kept, *a, **kw)
        return (out[0] * 2,) + tuple(out[1:]) if isinstance(out, tuple) else out * 2
    return f


def _plant(make):
    def plant() -> list:
        from repro.kernels import ops

        return [patch(ops, e, make) for e in COUNT_ENTRIES]
    return plant


FAULTS = {"answer_altered": _plant(_count_plus_one), "half_batch": _plant(_half_rows)}


# ---------------------------------------------------------------------------
# the kernel calls the roofline reader reads
# ---------------------------------------------------------------------------


@dataclass
class Call:
    """One concrete call of a support-count entry point."""

    entry: str  # the ops function called
    sites: int
    n_tx: int  # rows a site
    words: int  # 32-bit words a row
    masks: object  # the candidate masks, until resolved
    n_cand_total: int | None = None  # non-empty candidates over all sites

    def resolve(self) -> None:
        """Count the non-empty candidates (all-zero rows are padding) and
        drop the masks."""
        if self.masks is not None:
            m = np.asarray(self.masks).reshape(-1, self.words)
            self.n_cand_total = int((m != 0).any(axis=1).sum())
            self.masks = None


def _take(sites: bool):
    def take(entry, tx, masks, *args, **kw) -> Call:
        s, n, w = tx.shape if sites else (1, *tx.shape)
        return Call(entry=entry, sites=s, n_tx=n, words=w, masks=masks)
    return take


KERNELS = {e: _take(e.endswith("_sites")) for e in COUNT_ENTRIES}
