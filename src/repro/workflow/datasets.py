"""A served dataset: its rows, its versions and its site split.

``launch.serve.MiningService`` keeps one :class:`Dataset` per registered
name, the one place that knows a dataset's layout.  Transactions are
held packed, never dense (the delta-Apriori state's packed batches, and
one packed host table per version); points as the appended float32
batches, with the per-k centroids a warm-started ``kmeans`` starts
from.  Every append bumps ``version``, the result cache's key.
:meth:`Dataset.sites` is the only site split.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.apriori import DeltaApriori, TransactionDB
from repro.data.synthetic import split_indices, split_sites
from repro.workflow.registry import DATASET_KINDS


class Dataset:
    """One served dataset and its incremental state across appends."""

    def __init__(
        self, name: str, kind: str, *, n_items: int | None = None, dim: int | None = None,
        count_backend: str = "jnp",
    ):
        if kind not in DATASET_KINDS:
            raise ValueError(f"unknown dataset kind {kind!r}")
        self.name = name
        self.kind = kind
        self.version = 0
        # transactions: the delta-Apriori state (the packed batches) plus
        # the packed host table of the version it was built for
        self.n_items: int | None = None
        self.delta: DeltaApriori | None = None
        self._packed: np.ndarray | None = None
        self._packed_version = -1
        # points: appended (n, dim) batches plus per-k warm-start centroids
        self.dim: int | None = None
        self.pt_batches: list[np.ndarray] = []
        self.warm_centers: dict[int, np.ndarray] = {}  # k -> (k, dim)
        if kind == "transactions":
            if n_items is None:
                raise ValueError("transactions dataset needs n_items")
            self.n_items = int(n_items)
            self.delta = DeltaApriori(self.n_items, backend=count_backend)
        else:
            if dim is None:
                raise ValueError("points dataset needs dim")
            self.dim = int(dim)

    # -- appends --------------------------------------------------------------

    def append_transactions(self, dense_batch: np.ndarray) -> int:
        """Append one dense bool (n_tx, n_items) batch; folds into the
        delta-Apriori state and bumps ``version``.  Returns the version."""
        if self.kind != "transactions":
            raise ValueError(f"dataset {self.name!r} holds points, not transactions")
        self.delta.append(np.asarray(dense_batch, dtype=bool))
        self.version = self.delta.version
        return self.version

    def append_points(self, points: np.ndarray) -> int:
        """Append one (n, dim) point batch; bumps ``version``.  Previous
        per-k centroids are KEPT — they seed the next warm-started fit."""
        if self.kind != "points":
            raise ValueError(f"dataset {self.name!r} holds transactions, not points")
        pts = np.asarray(points, dtype=np.float32)
        if pts.ndim != 2 or pts.shape[1] != self.dim:
            raise ValueError(f"expected (n, {self.dim}) points, got {pts.shape}")
        self.pt_batches.append(pts)
        self.version += 1
        return self.version

    # -- views ----------------------------------------------------------------

    def points(self) -> np.ndarray:
        """Every appended point, in append order: (n, dim) float32."""
        return np.concatenate(self.pt_batches, axis=0)

    def packed(self) -> np.ndarray:
        """The whole transactions table, packed: (n_tx, W) uint32 on the
        host, rows in append order.  Rebuilt on the first call after an
        append; the host concatenation keeps every compile off the
        dataset's length."""
        if self._packed_version != self.version:
            parts = [np.asarray(db.packed) for db in self.delta.batches]
            table = parts[0] if len(parts) == 1 else np.concatenate(parts)
            # rows contiguous for the split's gathers: a TPU's host copy of
            # a device array can be column-major
            self._packed = np.ascontiguousarray(table)
            self._packed_version = self.version
        return self._packed

    def sites(self, n_sites: int, split_seed: int):
        """The dataset split across ``n_sites`` grid sites by one seeded
        permutation.  Transactions: one ``TransactionDB`` per site, its
        rows gathered from :meth:`packed` and uploaded — bit for bit
        ``TransactionDB.from_dense`` of ``split_transactions`` of the same
        rows, since packing works row by row.  Points: the (S, n, D)
        array of ``split_sites`` over :meth:`points`."""
        if self.kind == "points":
            return split_sites(self.points(), int(n_sites), seed=split_seed)
        packed = self.packed()
        return [
            TransactionDB(packed=jnp.asarray(np.take(packed, idx, axis=0)),
                          n_items=self.n_items, n_tx=len(idx))
            for idx in split_indices(len(packed), int(n_sites), seed=split_seed)
        ]
