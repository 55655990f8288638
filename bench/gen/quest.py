"""Vectorised IBM Quest transaction generator, after Agrawal and Srikant,
"Fast Algorithms for Mining Association Rules", VLDB 1994, section 2.4.3.

The source's semantics:

* ``n_patterns`` potentially large itemsets (|L|), each of Poisson size
  with mean ``avg_pattern_len`` (|I|).  The first draws its items at
  random; each later one takes a share of its items from the one before,
  the share drawn from an exponential with mean ``correlation``, and the
  rest at random.  Each has a weight drawn from Exp(1), normalised, and
  a corruption level drawn from a normal with mean ``corruption_mean``
  and variance ``corruption_var`` (clipped to [0, 0.99]).
* A transaction has a Poisson size with mean ``avg_tx_len`` (|T|).  It
  takes itemsets chosen by weight; an itemset is corrupted by dropping
  an item as long as a uniform draw is below its corruption level.  An
  itemset that does not fit goes in anyway in half the cases; in the
  other half the transaction ends and the itemset moves on.

Two differences of set-up, both deliberate and neither of distribution:

* an itemset that moves on is not carried to the next transaction; that
  transaction draws its first itemset afresh, from the same weights, and
  a transaction's first itemset always goes in (no row is empty);
* the pattern table comes from its own seed (the deployment's, fixed in
  the configuration), so every run seed mines the same structure and
  only the transactions differ; rows are drawn in blocks with NumPy.

``rows(data, seed)`` is the generator as the harness finds it by a
configuration's ``data.generator``; ``TINY`` is the size a CPU test runs
it at.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Patterns:
    items: np.ndarray  # (P, L) int32 item ids, -1 past each pattern's length
    lengths: np.ndarray  # (P,) int32
    weights: np.ndarray  # (P,) float64, sums to 1
    corruption: np.ndarray  # (P,) float64 in [0, 0.99]


def quest_patterns(seed: int, n_items: int, n_patterns: int, avg_pattern_len: float, *,
                   correlation: float = 0.5, corruption_mean: float = 0.5,
                   corruption_var: float = 0.1) -> Patterns:
    """The table of potentially large itemsets."""
    rng = np.random.default_rng(seed)
    weights = rng.exponential(1.0, n_patterns)
    weights /= weights.sum()
    lengths = np.maximum(1, np.minimum(n_items, rng.poisson(avg_pattern_len, n_patterns)))
    corruption = np.clip(rng.normal(corruption_mean, np.sqrt(corruption_var), n_patterns),
                         0.0, 0.99)
    items = np.full((n_patterns, int(lengths.max())), -1, dtype=np.int32)
    prev = np.zeros(0, dtype=np.int64)
    for p, ln in enumerate(lengths):
        n_same = min(int(round(min(1.0, rng.exponential(correlation)) * ln)), len(prev))
        same = rng.choice(prev, size=n_same, replace=False)
        rest = rng.choice(np.setdiff1d(np.arange(n_items), same), size=ln - n_same,
                          replace=False)
        prev = np.concatenate([same, rest])
        items[p, :ln] = prev
    return Patterns(items=items, lengths=lengths.astype(np.int32), weights=weights,
                    corruption=corruption)


def quest_transactions(seed, n_tx: int, n_items: int, patterns: Patterns, *,
                       avg_tx_len: float = 10.0, block_rows: int = 1 << 18) -> np.ndarray:
    """Dense bool (n_tx, n_items) transactions drawn from ``patterns``;
    ``seed`` is an int or a sequence of ints."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((n_tx, n_items), dtype=bool)
    cdf = np.cumsum(patterns.weights)
    cdf[-1] = 1.0
    n_pat = len(patterns.weights)
    log_c = np.log(np.maximum(patterns.corruption, 1e-300))
    for r0 in range(0, n_tx, block_rows):
        rows = min(block_rows, n_tx - r0)
        target = np.maximum(1, rng.poisson(avg_tx_len, rows))
        got = np.zeros(rows, dtype=np.int64)
        live = np.arange(rows)
        while live.size:
            # one itemset for every row that is still filling
            pick = np.minimum(np.searchsorted(cdf, rng.random(live.size), side="right"), n_pat - 1)
            its, ln = patterns.items[pick], patterns.lengths[pick]
            # items dropped: as long as a uniform draw is below the corruption
            drop = np.floor(np.log(rng.random(live.size)) / log_c[pick])
            drop = np.where(patterns.corruption[pick] > 0, np.minimum(drop, ln), 0)
            key = np.where(its >= 0, rng.random(its.shape), np.inf)
            rank = np.argsort(np.argsort(key, axis=1), axis=1)
            keep = (its >= 0) & (rank >= drop[:, None])
            rows_ix = r0 + live[:, None]
            new = (keep & ~dense[rows_ix, np.maximum(its, 0)]).sum(1)
            fits = got[live] + new <= target[live]
            put = fits | (rng.random(live.size) < 0.5) | (got[live] == 0)
            r, c = np.nonzero(keep & put[:, None])
            dense[r0 + live[r], its[r, c]] = True
            got[live] += np.where(put, new, 0)
            live = live[put & (got[live] < target[live])]
    return dense


# a size a test run holds: fewer rows and (for the interpreted kernels'
# sake) fewer items and patterns
TINY = {"n_tx": 2400, "n_items": 96, "n_patterns": 24}


def rows(data: dict, seed) -> np.ndarray:
    """The configuration's transactions (``data`` is its ``data`` group)
    from ``seed``, an int or a sequence of ints."""
    pats = quest_patterns(data["pattern_seed"], data["n_items"], data["n_patterns"],
                          data["avg_pattern_len"], correlation=data["correlation"],
                          corruption_mean=data["corruption_mean"],
                          corruption_var=data["corruption_var"])
    return quest_transactions(seed, data["n_tx"], data["n_items"], pats,
                              avg_tx_len=data["avg_tx_len"])
