"""The benchmark's data generators: the vectorised Quest generator
against its source's statistics and against the program's
loop-per-transaction ``ibm_transactions``; the itemset reference against
brute force."""

from __future__ import annotations

import numpy as np
import pytest
from benchutil import ROOT  # noqa: F401  puts the checkout on the path

from bench.gen.quest import quest_patterns, quest_transactions
from bench.reference import ItemsetReference

N_TX, N_ITEMS, N_PAT = 20_000, 1000, 2000


@pytest.fixture(scope="module")
def t10i4():
    pats = quest_patterns(1703, N_ITEMS, N_PAT, 4)
    return pats, quest_transactions(7, N_TX, N_ITEMS, pats)


def _top_item_supports(dense: np.ndarray, top: int = 20) -> np.ndarray:
    return np.sort(dense.mean(0))[::-1][:top]


def test_quest_follows_its_source(t10i4):
    pats, dense = t10i4
    # |T| = 10: a Poisson target, overshot only by itemsets put in anyway
    assert abs(dense.sum(1).mean() - 10) < 0.3 and dense.any(axis=1).all()
    # |I| = 4, corruption N(0.5, variance 0.1) clipped, weights sum to 1
    assert abs(pats.lengths.mean() - 4) < 0.15
    assert abs(pats.corruption.mean() - 0.5) < 0.03 and abs(pats.corruption.var() - 0.1) < 0.02
    assert pats.weights.sum() == pytest.approx(1.0)
    # correlation 0.5: successive itemsets share items far more often than
    # two drawn at random from 1,000 items would
    shared = np.mean([len(set(pats.items[p]) & set(pats.items[p - 1]) - {-1}) > 0
                      for p in range(1, N_PAT)])
    assert shared > 0.4


def test_quest_matches_ibm_transactions_in_distribution(t10i4):
    from repro.data.synthetic import ibm_transactions

    _, got = t10i4
    # a fixed per-item corruption of 0.25 drops one item of four on
    # average, as the source's geometric drops at a mean level of 0.5 do
    ref = ibm_transactions(7, N_TX, N_ITEMS, avg_tx_len=10, n_patterns=N_PAT, corruption=0.25)
    # mean transaction length: the program's loop overshoots the target by
    # its last pattern and adds 0-2 noise items; within 30 %
    assert abs(got.sum(1).mean() - ref.sum(1).mean()) < 0.3 * ref.sum(1).mean()
    # the most frequent items: supports of the same order (factor 3)
    g, r = _top_item_supports(got), _top_item_supports(ref)
    assert np.all(g < 3 * r) and np.all(r < 3 * g)


def test_quest_is_a_function_of_its_seeds():
    pats = quest_patterns(1703, 96, 24, 4)
    a = quest_transactions(2**31 + 5, 3000, 96, pats)
    assert np.array_equal(a, quest_transactions(2**31 + 5, 3000, 96, pats))
    assert not np.array_equal(a, quest_transactions(2**31 + 6, 3000, 96, pats))
    assert a.dtype == bool and a.any(axis=1).all()


def test_itemset_reference_against_brute_force():
    from itertools import combinations

    rng = np.random.default_rng(0)
    dense = rng.random((2000, 12)) < 0.3
    ref = ItemsetReference(dense)
    got = ref.frequent(120, 3)
    want = {}
    for k in (1, 2, 3):
        for its in combinations(range(12), k):
            c = int(dense[:, list(its)].all(axis=1).sum())
            if c >= 120:
                want[its] = c
    assert got == want
    bf16 = ItemsetReference(dense, "bfloat16").frequent(120, 3)
    assert bf16 != want  # counts above 256 are not exact in bfloat16
