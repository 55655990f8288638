"""The service's site split of a transactions dataset: a row gather of the
dataset's packed table, bit for bit the ``split_transactions`` +
``pack_bool_matrix`` split of the concatenated rows, rebuilt per dataset
version and drawn afresh for every request."""

from __future__ import annotations

import math

import numpy as np
import pytest

import repro.core.apriori as apriori
from repro.core.apriori import bruteforce_frequent, pack_bool_matrix
from repro.data.synthetic import split_transactions
from repro.launch.serve import MiningService
from repro.workflow.registry import get_workload

N_ITEMS = 40  # two packed words, the second one partial


def _batch(seed: int, n_tx: int) -> np.ndarray:
    return np.random.default_rng(seed).random((n_tx, N_ITEMS)) < 0.3


def _service(batches, n_sites: int = 3) -> MiningService:
    svc = MiningService(count_backend="jnp", use_kernel=False, n_sites=n_sites)
    svc.register_dataset("tx", "transactions", n_items=N_ITEMS)
    for b in batches:
        svc.append_transactions("tx", b)
    return svc


def _sites(svc, n_sites: int, split_seed: int) -> list:
    spec = get_workload("gfm")
    p = spec.resolve({"n_sites": n_sites, "split_seed": split_seed})
    return spec.site_split(svc._dataset("tx"), p, svc)


def _run(svc, app: str, params: dict):
    rid = svc.submit("t", app, "tx", params)
    while svc.poll(rid) != "done":
        assert svc.poll(rid) != "failed", svc.request(rid).error
        svc.step()
    return svc.result(rid)


BATCH_SIZES = {"one": (23,), "three": (11, 17, 5)}


@pytest.mark.parametrize("batches", sorted(BATCH_SIZES))
@pytest.mark.parametrize("n_sites", [1, 3, 7])
@pytest.mark.parametrize("split_seed", [0, 2**31 + 7])
def test_sites_equal_dense_split_packed(batches, n_sites, split_seed):
    dense = [_batch(100 + i, n) for i, n in enumerate(BATCH_SIZES[batches])]
    svc = _service(dense)
    got = _sites(svc, n_sites, split_seed)
    want = split_transactions(np.concatenate(dense), n_sites, seed=split_seed)
    assert len(got) == len(want) == n_sites
    for db, rows in zip(got, want):
        assert db.n_tx == len(rows)
        assert db.n_items == N_ITEMS
        assert db.packed.dtype == np.uint32
        np.testing.assert_array_equal(np.asarray(db.packed), pack_bool_matrix(rows))


@pytest.mark.parametrize("app", ["gfm", "fdm", "cd_apriori"])
def test_grid_answers_equal_reference(app):
    dense = [_batch(7, 30), _batch(8, 19)]
    svc = _service(dense)
    params = {"k": 3, "minsup": 0.12, "split_seed": 5}
    pooled = np.concatenate(dense)
    want = bruteforce_frequent(pooled, 3, math.ceil(0.12 * len(pooled)))
    assert want and any(len(its) > 1 for its in want)
    assert _run(svc, app, params).frequent == want


def test_append_rebuilds_packed_table_and_counts_new_rows(monkeypatch):
    first = _batch(1, 26)
    svc = _service([first])
    ds = svc._dataset("tx")
    params = {"k": 2, "minsup": 0.15}
    before = _run(svc, "gfm", params)
    assert before.frequent == bruteforce_frequent(first, 2, math.ceil(0.15 * 26))
    assert ds._packed_version == 1 and ds._packed.shape == (26, 2)

    second = _batch(2, 31)
    svc.append_transactions("tx", second)

    def _no_pack(dense):
        raise AssertionError("the split re-packed a dense table")

    monkeypatch.setattr(apriori, "pack_bool_matrix", _no_pack)
    after = _run(svc, "gfm", params)
    pooled = np.concatenate([first, second])
    assert after.n_total_tx == 57
    assert after.frequent == bruteforce_frequent(pooled, 2, math.ceil(0.15 * 57))
    assert ds._packed_version == 2
    np.testing.assert_array_equal(ds._packed, pack_bool_matrix(pooled))  # the unpatched one


def test_each_request_draws_its_own_split():
    svc = _service([_batch(3, 60)])
    ds = svc._dataset("tx")
    a = _sites(svc, 3, split_seed=1)
    table = ds.packed()
    b = _sites(svc, 3, split_seed=2)
    again = _sites(svc, 3, split_seed=1)
    assert ds.packed() is table  # the packed table is cached per version...
    assert table.flags.c_contiguous

    def rows(sites):
        return [{r.tobytes() for r in np.asarray(db.packed)} for db in sites]

    assert rows(a) != rows(b)  # ...the split is not
    assert rows(again) == rows(a)
    assert all(x.packed is not y.packed for x, y in zip(a, again))
