"""The served dataset (``workflow.datasets.Dataset``): appends, versions and
the one site split, and the service handing a request's resolved params
to the runtime unchanged."""

from __future__ import annotations

import ast
from pathlib import Path

import numpy as np
import pytest

from repro.data.synthetic import gaussian_mixture, split_sites
from repro.launch.serve import MiningService
from repro.runtime.gridruntime import GridRuntime
from repro.workflow.datasets import Dataset
from repro.workflow.registry import get_workload

N_ITEMS = 8


def _tx_batch(seed: int, n_tx: int) -> np.ndarray:
    return np.random.default_rng(seed).random((n_tx, N_ITEMS)) < 0.4


def _service(n_sites: int = 3) -> MiningService:
    return MiningService(count_backend="jnp", use_kernel=False, n_sites=n_sites)


def _run(svc, app: str, dataset: str, params: dict):
    rid = svc.submit("t", app, dataset, params)
    svc.drain()
    assert svc.poll(rid) == "done", svc.request(rid).error
    return svc.result(rid)


def _runtime() -> GridRuntime:
    return GridRuntime(count_backend="jnp", use_kernel=False, sync="pooled")


@pytest.mark.parametrize("n_sites", [1, 3])
@pytest.mark.parametrize("split_seed", [0, 5])
def test_point_sites_equal_split_of_concatenated_batches(n_sites, split_seed):
    ds = Dataset("pts", "points", dim=3)
    first, _ = gaussian_mixture(1, 31, 3, 2)
    second, _ = gaussian_mixture(2, 17, 3, 2)
    assert ds.append_points(first) == 1
    np.testing.assert_array_equal(ds.sites(n_sites, split_seed),
                                  split_sites(first, n_sites, seed=split_seed))
    assert ds.append_points(second) == 2
    got = ds.sites(n_sites, split_seed)
    want = split_sites(np.concatenate([first, second]), n_sites, seed=split_seed)
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_appends_check_kind_and_shape():
    with pytest.raises(ValueError, match="unknown dataset kind"):
        Dataset("x", "graphs")
    with pytest.raises(ValueError, match="needs n_items"):
        Dataset("tx", "transactions")
    with pytest.raises(ValueError, match="needs dim"):
        Dataset("pts", "points")
    tx = Dataset("tx", "transactions", n_items=N_ITEMS)
    pts = Dataset("pts", "points", dim=2)
    with pytest.raises(ValueError, match="'tx' holds transactions, not points"):
        tx.append_points(np.zeros((3, 2)))
    with pytest.raises(ValueError, match="'pts' holds points, not transactions"):
        pts.append_transactions(np.zeros((3, N_ITEMS), dtype=bool))
    with pytest.raises(ValueError, match=r"expected \(n, 2\) points"):
        pts.append_points(np.zeros((3, 4)))
    assert (tx.version, pts.version) == (0, 0)
    assert tx.append_transactions(_tx_batch(0, 5)) == 1
    assert tx.append_transactions(_tx_batch(1, 4)) == 2
    assert tx.delta.n_tx == 9 and tx.packed().shape == (9, 1)


def test_datasets_module_imports_nothing_from_launch():
    src = Path(__file__).resolve().parents[1] / "src" / "repro" / "workflow" / "datasets.py"
    names = []
    for node in ast.walk(ast.parse(src.read_text())):
        if isinstance(node, ast.ImportFrom):
            names.append(node.module or "")
        elif isinstance(node, ast.Import):
            names.extend(a.name for a in node.names)
    assert names and not [n for n in names if n.startswith("repro.launch")]


def test_gfm_local_minsup_reaches_the_run():
    """A tenant's ``local_minsup`` is run, not dropped: the served digest
    equals ``GridRuntime.run`` with the same params on the same split, and
    differs from the default (``local_minsup`` = ``minsup``)."""
    svc = _service()
    svc.register_dataset("tx", "transactions", n_items=N_ITEMS)
    svc.append_transactions("tx", _tx_batch(3, 90))
    spec = get_workload("gfm")
    params = {"k": 3, "minsup": 0.3, "split_seed": 1}
    low = {**params, "local_minsup": 0.1}
    sites = svc._dataset("tx").sites(3, 1)
    want = spec.digest(_runtime().run("gfm", sites, {**low, "n_sites": 3}).result)
    default = spec.digest(_runtime().run("gfm", sites, params).result)
    assert want != default
    assert spec.digest(_run(svc, "gfm", "tx", low)) == want
    assert spec.digest(_run(svc, "gfm", "tx", params)) == default


def test_served_vclustering_equals_runtime_on_the_same_split():
    """The builder derives the PRNG key and the config from ``seed``,
    ``k_local`` and ``iters``: the served run equals the runtime's on the
    dataset's own split."""
    svc = _service(n_sites=4)
    svc.register_dataset("pts", "points", dim=2)
    pts, _ = gaussian_mixture(4, 120, 2, 3)
    svc.append_points("pts", pts)
    params = {"k_local": 3, "iters": 4, "seed": 2, "split_seed": 3}
    spec = get_workload("vclustering")
    sites = svc._dataset("pts").sites(4, 3)
    want = spec.digest(_runtime().run("vclustering", sites, params).result)
    assert spec.digest(_run(svc, "vclustering", "pts", params)) == want


def test_fused_gfm_keeps_each_members_local_minsup():
    """Two gfm requests that differ only in ``local_minsup`` share a fusion
    signature and run as one dispatch; each keeps its own threshold."""
    svc = _service()
    svc.register_dataset("tx", "transactions", n_items=N_ITEMS)
    svc.append_transactions("tx", _tx_batch(3, 90))
    spec = get_workload("gfm")
    params = {"k": 3, "minsup": 0.3, "split_seed": 1}
    low = {**params, "local_minsup": 0.1}
    sites = svc._dataset("tx").sites(3, 1)
    want = [spec.digest(_runtime().run("gfm", sites, p).result) for p in (low, params)]
    rids = [svc.submit("t", "gfm", "tx", p) for p in (low, params)]
    svc.step()
    assert svc.fused_requests == 2 and svc.device_dispatches == 1
    assert [spec.digest(svc.result(r)) for r in rids] == want
