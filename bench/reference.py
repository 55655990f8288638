"""The plain reference, written from the semantics alone.

It imports nothing of the program and takes nothing it made: only the
transactions the benchmark generated and the request's parameters.

Frequent itemsets: level-wise Apriori over the dataset's dense 0/1
table.  A candidate's support is the number of rows whose dot
product with the candidate's 0/1 item vector equals its size: an int8
matrix product with int32 accumulation on the device, exact for any row
count below 2**31.  ``count_dtype`` is the control's knob: counts held
in bfloat16 break the exactness that the configuration guarantees.
"""

from __future__ import annotations

import functools
import math
from itertools import combinations

import numpy as np

ROW_BLOCK = 1 << 16
CAND_BLOCK = 256


def min_count(minsup: float, n_tx: int) -> int:
    """Frequent means support >= minsup * n_tx (at least 1)."""
    return max(1, int(math.ceil(minsup * n_tx)))


def apriori_gen(frequent: list[tuple[int, ...]]) -> list[tuple[int, ...]]:
    """Candidates of size k+1 from the frequent k-itemsets: joins of two
    itemsets that share their first k-1 items, kept only when every
    k-subset is frequent."""
    freq = set(frequent)
    by_prefix: dict[tuple[int, ...], list[int]] = {}
    for its in sorted(frequent):
        by_prefix.setdefault(its[:-1], []).append(its[-1])
    out = []
    for prefix, lasts in by_prefix.items():
        for a, b in combinations(lasts, 2):
            cand = prefix + (a, b)
            if all(sub in freq for sub in combinations(cand, len(cand) - 1)):
                out.append(cand)
    return out


@functools.lru_cache(maxsize=4)
def _counter(count_dtype: str):
    import jax
    import jax.numpy as jnp

    acc = jnp.dtype(count_dtype)

    @jax.jit
    def count(x, masks, sizes):
        # x (R, I) int8 with R a multiple of ROW_BLOCK; masks (C, I) int8;
        # sizes (C,) int32 -> (C,) supports held in ``acc``
        def body(b, total):
            rows = jax.lax.dynamic_slice_in_dim(x, b * ROW_BLOCK, ROW_BLOCK, 0)
            hits = jax.lax.dot_general(rows, masks, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.int32)
            inside = (hits == sizes[None, :]).astype(acc)
            return total + jnp.sum(inside, axis=0, dtype=acc)

        zero = jnp.zeros(masks.shape[0], acc)
        return jax.lax.fori_loop(0, x.shape[0] // ROW_BLOCK, body, zero)

    return count


class ItemsetReference:
    """Apriori over one dense (n_tx, n_items) bool table, kept on the
    device as int8 rows padded with empty transactions."""

    def __init__(self, dense: np.ndarray, count_dtype: str = "int32"):
        import jax.numpy as jnp

        self.n_tx, self.n_items = dense.shape
        rows = -(-self.n_tx // ROW_BLOCK) * ROW_BLOCK
        x = np.zeros((rows, self.n_items), np.int8)
        x[: self.n_tx] = dense
        self.x = jnp.asarray(x)
        self.count_dtype = count_dtype
        self._known: dict[tuple[int, ...], int] = {}
        self._floor: int | None = None  # lowest threshold mined so far

    def supports(self, itemsets: list[tuple[int, ...]]) -> list[int]:
        import jax.numpy as jnp

        if not itemsets:
            return []
        c = -(-len(itemsets) // CAND_BLOCK) * CAND_BLOCK
        masks = np.zeros((c, self.n_items), np.int8)
        sizes = np.full(c, self.n_items + 1, np.int32)  # pad rows never match
        for j, its in enumerate(itemsets):
            masks[j, list(its)] = 1
            sizes[j] = len(its)
        got = _counter(self.count_dtype)(self.x, jnp.asarray(masks), jnp.asarray(sizes))
        return [int(v) for v in np.asarray(got.astype(jnp.float32))[: len(itemsets)]]

    def frequent(self, threshold: int, k_max: int) -> dict[tuple[int, ...], int]:
        """Every itemset of size 1..k_max with support >= ``threshold``."""
        if self._floor is None or threshold < self._floor:
            self._mine(threshold, k_max)
        return {its: c for its, c in self._known.items()
                if c >= threshold and len(its) <= k_max}

    def _mine(self, threshold: int, k_max: int) -> None:
        known: dict[tuple[int, ...], int] = {}
        level = [(i,) for i in range(self.n_items)]
        for _ in range(k_max):
            if not level:
                break
            counts = self.supports(level)
            freq = [its for its, c in zip(level, counts) if c >= threshold]
            known.update({its: c for its, c in zip(level, counts) if c >= threshold})
            level = apriori_gen(freq)
        self._known, self._floor = known, threshold
