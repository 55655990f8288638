"""Apriori substrate: packed-bitmap transaction DBs + candidate machinery.

Transactions are bitmaps over a fixed item universe, packed 32 items/word
(uint32).  Support counting — the compute hot-spot — is `AND + compare +
reduce` over (transactions x candidates) tiles and is served either by the
pure-jnp oracle here or by the Pallas TPU kernel in
``repro.kernels.support_count`` (selected via ``count_backend``).

Candidate *generation* (level-wise join + prune) is classic set algebra
with data-dependent sizes; it stays on host exactly as in the paper, where
the protocol is orchestrated at the grid-job level anyway.

Each level's phases open profiler spans (``repro.obs.span``):
``repro.level.join`` (candidate generation), ``repro.level.stage`` (a
count's inputs: the candidates packed into masks and, in the fused
site-axis forms, the padded site tables, all uploaded),
``repro.level.count`` (the device dispatch and the wait for its
counts), ``repro.level.count1`` (the singleton count on the host) and
``repro.level.fold`` (counts into per-site dicts and frequent lists).
"""

from __future__ import annotations

import functools
from collections.abc import Iterable, Sequence
from dataclasses import dataclass
from itertools import combinations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span

Itemset = tuple[int, ...]  # always sorted


# ---------------------------------------------------------------------------
# Packed-bitmap DB
# ---------------------------------------------------------------------------


def n_words(n_items: int) -> int:
    return (n_items + 31) // 32


def pack_bool_matrix(dense: np.ndarray) -> np.ndarray:
    """(N, n_items) bool -> (N, W) uint32, bit i of word w = item 32*w+i."""
    n, m = dense.shape
    w = n_words(m)
    padded = np.zeros((n, w * 32), dtype=bool)
    padded[:, :m] = dense
    bits = padded.reshape(n, w, 32)
    weights = (1 << np.arange(32, dtype=np.uint64)).astype(np.uint64)
    words = (bits.astype(np.uint64) * weights[None, None, :]).sum(axis=-1)
    return words.astype(np.uint32)


def pack_itemsets(itemsets: Sequence[Itemset], n_items: int) -> np.ndarray:
    """List of itemsets -> (C, W) uint32 masks."""
    w = n_words(n_items)
    out = np.zeros((max(len(itemsets), 1), w), dtype=np.uint32)
    for c, its in enumerate(itemsets):
        for item in its:
            out[c, item // 32] |= np.uint32(1) << np.uint32(item % 32)
    return out


@dataclass(frozen=True)
class TransactionDB:
    """One site's transaction database."""

    packed: jax.Array  # (n_tx, W) uint32
    n_items: int
    n_tx: int

    @staticmethod
    def from_dense(dense: np.ndarray) -> "TransactionDB":
        return TransactionDB(
            packed=jnp.asarray(pack_bool_matrix(dense)),
            n_items=dense.shape[1],
            n_tx=dense.shape[0],
        )


# ---------------------------------------------------------------------------
# Support counting (jnp oracle; kernel behind the same signature)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def _count_block(db: jax.Array, masks: jax.Array) -> jax.Array:
    """(N, W) uint32, (C, W) uint32 -> (C,) int32 supports."""
    hit = (db[:, None, :] & masks[None, :, :]) == masks[None, :, :]  # (N, C, W)
    return jnp.sum(jnp.all(hit, axis=-1), axis=0).astype(jnp.int32)


def count_supports(
    db: TransactionDB,
    itemsets: Sequence[Itemset],
    backend: str = "jnp",
    block_c: int = 512,
) -> np.ndarray:
    """Support counts for ``itemsets`` on one site's DB.  Returns (C,) int64."""
    if not itemsets:
        return np.zeros((0,), dtype=np.int64)
    with span("repro.level.stage"):
        masks_np = pack_itemsets(itemsets, db.n_items)
    if backend == "kernel":
        from repro.kernels import ops

        with span("repro.level.count"):
            out = ops.support_count(db.packed, jnp.asarray(masks_np))
            return np.asarray(out, dtype=np.int64)
    with span("repro.level.count"):
        outs = []
        for s in range(0, masks_np.shape[0], block_c):
            outs.append(np.asarray(_count_block(db.packed, jnp.asarray(masks_np[s : s + block_c]))))
        return np.concatenate(outs).astype(np.int64)


def count_supports_prune(
    db: TransactionDB,
    itemsets: Sequence[Itemset],
    min_count: int,
    backend: str = "jnp",
    block_c: int = 512,
) -> tuple[np.ndarray, np.ndarray]:
    """Counts AND the ``>= min_count`` frequent mask for one site's level
    in a single pass — ``(counts (C,) int64, frequent (C,) bool)`` with
    ``frequent == counts >= min_count`` exactly.  On the kernel backend
    the threshold is fused into the device pass
    (``ops.support_count_prune``), so the level loop's hygiene step stops
    being a host round-trip of the raw count vector; the jnp oracle
    thresholds on host behind the identical signature."""
    if not itemsets:
        return np.zeros((0,), dtype=np.int64), np.zeros((0,), dtype=bool)
    if backend == "kernel":
        from repro.kernels import ops

        with span("repro.level.stage"):
            masks_np = pack_itemsets(itemsets, db.n_items)
        with span("repro.level.count"):
            cnt, freq = ops.support_count_prune(db.packed, jnp.asarray(masks_np), int(min_count))
            return np.asarray(cnt, dtype=np.int64), np.asarray(freq)
    sup = count_supports(db, itemsets, backend=backend, block_c=block_c)
    return sup, sup >= int(min_count)


def _cand_bucket(n: int, step: int = 64) -> int:
    """Round a candidate count up to a bucket so the fused counting jit
    compiles O(log) distinct shapes instead of one per level."""
    return max(step, ((n + step - 1) // step) * step)


@jax.jit
def _count_block_sites(dbs: jax.Array, masks: jax.Array) -> jax.Array:
    """(S, N, W) uint32, (S, C, W) uint32 -> (S, C) int32 — the fused
    site-axis form of ``_count_block``: one device dispatch for the
    whole fan-out."""
    return jax.vmap(_count_block)(dbs, masks)


def _stage_sites(
    dbs: Sequence[TransactionDB], lists: list[list[Itemset]], live: list[int], w: int
) -> tuple[jax.Array, jax.Array]:
    """The live sites' tables and candidate masks, padded to one
    ``(S, N, W)`` and one ``(S, C, W)`` array on the host and uploaded."""
    n_max = max(dbs[i].n_tx for i in live)
    c_max = _cand_bucket(max(len(lists[i]) for i in live))
    tx_s = np.zeros((len(live), n_max, w), dtype=np.uint32)
    masks_s = np.zeros((len(live), c_max, w), dtype=np.uint32)
    for row, i in enumerate(live):
        tx_s[row, : dbs[i].n_tx] = np.asarray(dbs[i].packed)
        masks_s[row, : len(lists[i])] = pack_itemsets(lists[i], dbs[i].n_items)
    return jnp.asarray(tx_s), jnp.asarray(masks_s)


def fused_count_sites(
    dbs: Sequence[TransactionDB],
    itemset_lists: Sequence[Sequence[Itemset]],
    backend: str = "jnp",
) -> list[np.ndarray]:
    """Count each site's OWN candidate list with ONE device dispatch
    across the site axis — the fused form of per-site ``count_supports``
    loops that the batched execution backend uses for the ``apriori_i``
    / ``recount_i`` / FDM count fan-outs.

    Sites are padded to a common shape: transactions to the max ``n_tx``
    (all-zero rows match no non-empty mask, so padded rows count zero
    support) and candidates to a bucketed max count (padded all-zero
    masks produce garbage counts that are sliced away per site before
    returning).  Returns one (C_i,) int64 array per site, exactly equal
    to ``count_supports(dbs[i], itemset_lists[i])``.

    Falls back to the per-site loop when the sites disagree on the item
    universe (no common mask width) — correctness first, fusion when
    legal.

    The "site" axis is purely positional: under cross-request batching
    (``GridRuntime.run_many``) the entries may come from DIFFERENT
    requests mining the same dataset, so nothing here may assume the
    lists share a threshold or a candidate pool — each position is
    counted against its own list only.
    """
    lists = [list(lst) for lst in itemset_lists]
    if len(dbs) != len(lists):
        raise ValueError(f"{len(dbs)} sites but {len(lists)} candidate lists")
    empty = np.zeros((0,), dtype=np.int64)
    live = [i for i, lst in enumerate(lists) if lst]
    out: list[np.ndarray] = [empty] * len(lists)
    if not live:
        return out
    widths = {n_words(dbs[i].n_items) for i in live}
    if len(widths) != 1:
        # heterogeneous item universes cannot share one mask layout
        for i in live:
            out[i] = count_supports(dbs[i], lists[i], backend=backend)
        return out
    with span("repro.level.stage"):
        tx_s, masks_s = _stage_sites(dbs, lists, live, widths.pop())
    with span("repro.level.count"):
        if backend == "kernel":
            from repro.kernels import ops

            counts = np.asarray(ops.support_count_sites(tx_s, masks_s))
        else:
            counts = np.asarray(_count_block_sites(tx_s, masks_s))
    for row, i in enumerate(live):
        out[i] = counts[row, : len(lists[i])].astype(np.int64)
    return out


def fused_prune_sites(
    dbs: Sequence[TransactionDB],
    itemset_lists: Sequence[Sequence[Itemset]],
    min_counts: Sequence[int],
    backend: str = "jnp",
) -> list[tuple[np.ndarray, np.ndarray]]:
    """The prune-fused form of :func:`fused_count_sites`: one device
    dispatch counts every site's own candidate list AND thresholds it
    against that site's ``min_counts[i]`` (a per-site traced operand, so
    heterogeneous thresholds ride the same launch).  Returns one
    ``(counts (C_i,) int64, frequent (C_i,) bool)`` pair per site, with
    ``counts`` exactly equal to ``fused_count_sites`` and ``frequent ==
    counts >= min_counts[i]``.  Same padding rules, heterogeneous-
    universe fallback, and positional-axis contract as the count-only
    form — per-position ``min_counts`` is what lets one launch serve
    members of different requests (different ``minsup``) under
    cross-request batching, since the threshold is a traced operand and
    never a compile-time constant."""
    lists = [list(lst) for lst in itemset_lists]
    if len(dbs) != len(lists):
        raise ValueError(f"{len(dbs)} sites but {len(lists)} candidate lists")
    if len(dbs) != len(min_counts):
        raise ValueError(f"{len(dbs)} sites but {len(min_counts)} thresholds")
    empty = (np.zeros((0,), dtype=np.int64), np.zeros((0,), dtype=bool))
    live = [i for i, lst in enumerate(lists) if lst]
    out: list[tuple[np.ndarray, np.ndarray]] = [empty] * len(lists)
    if not live:
        return out
    widths = {n_words(dbs[i].n_items) for i in live}
    if len(widths) != 1:
        for i in live:
            out[i] = count_supports_prune(dbs[i], lists[i], min_counts[i], backend=backend)
        return out
    with span("repro.level.stage"):
        tx_s, masks_s = _stage_sites(dbs, lists, live, widths.pop())
        mc = np.asarray([int(min_counts[i]) for i in live], dtype=np.int32)
    with span("repro.level.count"):
        if backend == "kernel":
            from repro.kernels import ops

            counts, freq = ops.support_count_prune_sites(tx_s, masks_s, jnp.asarray(mc))
            counts, freq = np.asarray(counts), np.asarray(freq)
        else:
            counts = np.asarray(_count_block_sites(tx_s, masks_s))
            freq = counts >= mc[:, None]
    for row, i in enumerate(live):
        c_i = len(lists[i])
        out[i] = (counts[row, :c_i].astype(np.int64), freq[row, :c_i])
    return out


def item_supports(db: TransactionDB) -> np.ndarray:
    """Singleton supports (L1 seed) via bit-unpack + column sum."""
    with span("repro.level.count1"):
        words = np.asarray(db.packed)  # (N, W)
        bits = ((words[:, :, None] >> np.arange(32, dtype=np.uint32)[None, None, :]) & 1).astype(np.int64)
        cols = bits.reshape(words.shape[0], -1)[:, : db.n_items]
        return cols.sum(axis=0)


# ---------------------------------------------------------------------------
# Candidate generation (host-side set algebra)
# ---------------------------------------------------------------------------


def apriori_join(prev_frequent: Iterable[Itemset]) -> list[Itemset]:
    """F(k-1) x F(k-1) prefix join + downward-closure prune."""
    with span("repro.level.join"):
        prev = sorted(set(prev_frequent))
        prev_set = set(prev)
        if not prev:
            return []
        k_1 = len(prev[0])
        out = []
        for a_i in range(len(prev)):
            a = prev[a_i]
            for b_i in range(a_i + 1, len(prev)):
                b = prev[b_i]
                if a[:-1] != b[:-1]:
                    break  # sorted ⇒ shared prefix block is contiguous
                cand = a + (b[-1],)
                # prune: every (k)-subset must be in prev_set
                if all(tuple(sub) in prev_set for sub in combinations(cand, k_1)):
                    out.append(cand)
        return out


def subsets_of(itemset: Itemset) -> list[Itemset]:
    """Immediate (size-1 smaller) subsets."""
    return [tuple(s) for s in combinations(itemset, len(itemset) - 1)]


# ---------------------------------------------------------------------------
# Site-local Apriori (paper Alg 2 line 2: apriori_gen(X_i, k))
# ---------------------------------------------------------------------------


@dataclass
class LocalMineResult:
    """All itemsets COUNTED locally, with counts; `frequent[k]` lists the
    locally frequent ones per level.  Counts are cached so the global phase
    never re-counts something this site already measured."""

    counts: dict[Itemset, int]
    frequent: dict[int, list[Itemset]]
    count_calls: int  # device count invocations (for perf accounting)
    candidates_counted: int


def local_apriori(
    db: TransactionDB,
    k_max: int,
    min_count: int,
    backend: str = "jnp",
) -> LocalMineResult:
    """Level-wise Apriori with LOCAL pruning only (GFM phase 1)."""
    counts: dict[Itemset, int] = {}
    frequent: dict[int, list[Itemset]] = {}
    calls = 0
    n_cand = 0

    sup1 = item_supports(db)
    with span("repro.level.fold"):
        for item, c in enumerate(sup1):
            counts[(int(item),)] = int(c)
        frequent[1] = [(int(i),) for i in np.nonzero(sup1 >= min_count)[0]]
    calls += 1
    n_cand += db.n_items

    level = 1
    while level < k_max and frequent.get(level):
        cands = apriori_join(frequent[level])
        level += 1
        if not cands:
            frequent[level] = []
            break
        sup, freq = count_supports_prune(db, cands, min_count, backend=backend)
        calls += 1
        n_cand += len(cands)
        with span("repro.level.fold"):
            for its, c in zip(cands, sup):
                counts[its] = int(c)
            frequent[level] = [its for its, f in zip(cands, freq) if f]
    for lv in range(1, k_max + 1):
        frequent.setdefault(lv, [])
    return LocalMineResult(counts=counts, frequent=frequent, count_calls=calls, candidates_counted=n_cand)


def batched_local_apriori(
    dbs: Sequence[TransactionDB],
    k_max: int,
    min_counts: Sequence[int],
    backend: str = "jnp",
) -> list[LocalMineResult]:
    """Phase-1 local Apriori for ALL sites in lockstep: per level, every
    site generates its candidates on host, then ONE fused device
    dispatch (``fused_count_sites``) counts every site's candidates
    across the site axis.  Result-identical to per-site
    ``local_apriori`` calls — same candidates (generation depends only
    on each site's own frequents), same exact integer counts, same
    ``count_calls`` ledger (which counts the protocol's logical
    per-site count rounds, not device dispatches) — but the fan-out
    costs one kernel launch per level instead of one per site-level.

    ``min_counts`` is per position for the same reason it is in
    ``fused_prune_sites``: a cross-request fused wave mines the same
    shards under different thresholds, and sites exhaust (leave
    ``active``) independently — a position that stops generating
    candidates at level l must not drag its wave-mates down with it.
    """
    if len(dbs) != len(min_counts):
        raise ValueError(f"{len(dbs)} sites but {len(min_counts)} thresholds")
    res: list[LocalMineResult] = []
    for db, min_count in zip(dbs, min_counts):
        counts: dict[Itemset, int] = {}
        sup1 = item_supports(db)
        with span("repro.level.fold"):
            for item, c in enumerate(sup1):
                counts[(int(item),)] = int(c)
            res.append(
                LocalMineResult(
                    counts=counts,
                    frequent={1: [(int(i),) for i in np.nonzero(sup1 >= min_count)[0]]},
                    count_calls=1,
                    candidates_counted=db.n_items,
                )
            )
    level = 1
    active = set(range(len(dbs)))
    while level < k_max and active:
        cands_by: list[list[Itemset]] = [[] for _ in dbs]
        for i in list(active):
            if not res[i].frequent.get(level):
                active.discard(i)  # this site's search is exhausted
                continue
            cands_by[i] = apriori_join(res[i].frequent[level])
        level += 1
        sups = fused_prune_sites(dbs, cands_by, min_counts, backend=backend)
        with span("repro.level.fold"):
            for i in list(active):
                cands = cands_by[i]
                if not cands:
                    res[i].frequent[level] = []
                    active.discard(i)
                    continue
                res[i].count_calls += 1
                res[i].candidates_counted += len(cands)
                cnt_i, freq_i = sups[i]
                for its, c in zip(cands, cnt_i):
                    res[i].counts[its] = int(c)
                res[i].frequent[level] = [its for its, f in zip(cands, freq_i) if f]
    for lm in res:
        for lv in range(1, k_max + 1):
            lm.frequent.setdefault(lv, [])
    return res


# ---------------------------------------------------------------------------
# Delta (incremental) Apriori — the serving layer's hot repeated query
# ---------------------------------------------------------------------------


def concat_dbs(dbs: Sequence[TransactionDB]) -> TransactionDB:
    """Concatenate same-universe TransactionDBs along the transaction
    axis (the from-scratch view of an appended stream)."""
    if not dbs:
        raise ValueError("concat_dbs needs at least one TransactionDB")
    universes = {db.n_items for db in dbs}
    if len(universes) != 1:
        raise ValueError(f"cannot concat DBs over different item universes: {sorted(universes)}")
    return TransactionDB(
        packed=jnp.concatenate([db.packed for db in dbs], axis=0),
        n_items=dbs[0].n_items,
        n_tx=sum(db.n_tx for db in dbs),
    )


class DeltaApriori:
    """Incremental frequent-itemset state over an append-only transaction
    stream — the delta-maintenance entry point the continuous mining
    service (``launch.serve``) queries repeatedly.

    Support counts are ADDITIVE over transactions, which is the whole
    trick (the FUP family of incremental Apriori algorithms; the Apriori
    performance study of arXiv:1903.03008 motivates exactly this as the
    hot repeated query): every itemset this state has ever counted keeps
    an exact cumulative count, and :meth:`append` extends each of them
    with one support-count pass over the NEW batch only — O(|delta|)
    device work instead of O(|stream|).  A :meth:`query` then replays the
    level-wise Apriori loop, serving candidates from the cumulative cache
    for free and counting only candidates it has never seen — over the
    full concatenated stream, so their counts are exact too.

    Correctness contract (property-tested): ``query(k_max, min_count)``
    is BIT-IDENTICAL — same per-level frequent itemsets, same exact
    integer counts for every generated candidate — to
    ``local_apriori(concat_dbs(batches), k_max, min_count)`` run from
    scratch, for every append history and every threshold.  Candidate
    generation depends only on the (identical) frequents, and every
    served count equals the from-scratch count by additivity, so the
    equality holds by induction over levels.  Only the ``count_calls``
    ledger differs: it counts the DEVICE passes this instance actually
    ran, which is the saving being bought.

    ``version`` increments per append — the cache key the serving layer
    uses to guarantee a result is never served across a data change.
    """

    def __init__(self, n_items: int, backend: str = "jnp"):
        self.n_items = int(n_items)
        self.backend = backend
        self.version = 0  # bumped per append — the dataset_version key
        self._batches: list[TransactionDB] = []
        self._full: TransactionDB | None = None  # lazy concat of batches
        # cumulative exact counts over ALL appended transactions, for
        # every itemset ever counted (singletons always included)
        self._counts: dict[Itemset, int] = {(i,): 0 for i in range(self.n_items)}
        self.count_calls = 0  # lifetime device count passes (the ledger)

    @classmethod
    def from_db(cls, db: TransactionDB, backend: str = "jnp") -> "DeltaApriori":
        """Seed incremental state from an already-packed DB (one singleton
        pass, no dense round-trip) — how a grid site wraps its local shard
        so per-level candidate counts serve from the cumulative cache."""
        st = cls(db.n_items, backend=backend)
        sup1 = item_supports(db)
        st.count_calls += 1
        with span("repro.level.fold"):
            for item, c in enumerate(sup1):
                st._counts[(int(item),)] += int(c)
        st._batches.append(db)
        st._full = db
        st.version = 1
        return st

    @property
    def n_tx(self) -> int:
        return sum(db.n_tx for db in self._batches)

    @property
    def batches(self) -> tuple[TransactionDB, ...]:
        """The appended batches, packed, in append order."""
        return tuple(self._batches)

    def stream(self) -> TransactionDB:
        """The full appended stream as one DB (lazy concat, cached)."""
        if not self._batches:
            raise RuntimeError("DeltaApriori.stream before any append")
        if self._full is None:
            self._full = concat_dbs(self._batches)
        return self._full

    def uncached(self, itemsets: Iterable[Itemset]) -> list[Itemset]:
        """The subset of ``itemsets`` this state has never counted."""
        return [its for its in itemsets if its not in self._counts]

    def fold_exact(self, itemsets: Sequence[Itemset], counts) -> None:
        """Install exact full-stream counts computed EXTERNALLY (e.g. by a
        fused site-axis dispatch).  Caller contract: ``counts[i]`` is the
        support of ``itemsets[i]`` over the whole appended stream — the
        cumulative invariant extends to them as if counted here.  Ledgers
        one device pass when non-empty."""
        if not itemsets:
            return
        self.count_calls += 1
        with span("repro.level.fold"):
            for its, c in zip(itemsets, counts):
                self._counts[its] = int(c)

    def counts_for(self, itemsets: Sequence[Itemset]) -> dict[Itemset, int]:
        """Exact cumulative counts for arbitrary itemsets, counting only
        the never-seen ones (at most one device pass); cached itemsets are
        served for free — the local-pass entry point for workloads that
        bring their own candidate lists (count-distribution Apriori)."""
        with span("repro.level.fold"):
            self._count_new(self.uncached(itemsets))
            return {its: self._counts[its] for its in itemsets}

    def append(self, dense_batch: np.ndarray) -> int:
        """Fold one appended transaction batch into the cumulative counts
        (one singleton pass + one cached-itemset count pass over the new
        batch only) and bump ``version``.  Returns the new version."""
        if dense_batch.shape[1] != self.n_items:
            raise ValueError(
                f"batch has {dense_batch.shape[1]} items, state tracks {self.n_items}"
            )
        db = TransactionDB.from_dense(np.asarray(dense_batch, dtype=bool))
        sup1 = item_supports(db)
        self.count_calls += 1
        for item, c in enumerate(sup1):
            self._counts[(int(item),)] += int(c)
        cached = [its for its in self._counts if len(its) > 1]
        if cached:
            sup = count_supports(db, cached, backend=self.backend)
            self.count_calls += 1
            for its, c in zip(cached, sup):
                self._counts[its] += int(c)
        self._batches.append(db)
        self._full = None
        self.version += 1
        return self.version

    def _count_new(self, cands: list[Itemset]) -> None:
        """Count never-seen candidates over the full stream (exact, so the
        cumulative-cache invariant extends to them)."""
        if not cands:
            return
        if self._full is None:
            self._full = concat_dbs(self._batches)
        sup = count_supports(self._full, cands, backend=self.backend)
        self.count_calls += 1
        for its, c in zip(cands, sup):
            self._counts[its] = int(c)

    def query(self, k_max: int, min_count: int) -> LocalMineResult:
        """Level-wise Apriori over everything appended so far, serving
        counts from the cumulative cache.  Returns a ``LocalMineResult``
        bit-identical (counts + frequents) to a from-scratch
        ``local_apriori`` over the concatenated stream; its
        ``count_calls`` field reports the device passes THIS query cost
        (0 when every candidate was already cached)."""
        if not self._batches:
            raise RuntimeError("DeltaApriori.query before any append")
        calls0 = self.count_calls
        counts: dict[Itemset, int] = {}
        frequent: dict[int, list[Itemset]] = {}
        n_cand = self.n_items
        for i in range(self.n_items):
            counts[(i,)] = self._counts[(i,)]
        frequent[1] = [(i,) for i in range(self.n_items) if counts[(i,)] >= min_count]
        level = 1
        while level < k_max and frequent.get(level):
            cands = apriori_join(frequent[level])
            level += 1
            if not cands:
                frequent[level] = []
                break
            fresh = [its for its in cands if its not in self._counts]
            n_cand += len(cands)
            if fresh and len(fresh) == len(cands):
                # cold level (every candidate is new — the first query on
                # freshly appended data): one fused count+threshold pass
                # serves counts AND frequents, instead of a count pass
                # plus a host threshold sweep
                cnt, freq = count_supports_prune(
                    self.stream(), cands, min_count, backend=self.backend
                )
                self.count_calls += 1
                for its, c in zip(cands, cnt):
                    self._counts[its] = int(c)
                    counts[its] = int(c)
                frequent[level] = [its for its, f in zip(cands, freq) if f]
                continue
            self._count_new(fresh)
            for its in cands:
                counts[its] = self._counts[its]
            frequent[level] = [its for its in cands if counts[its] >= min_count]
        for lv in range(1, k_max + 1):
            frequent.setdefault(lv, [])
        return LocalMineResult(
            counts=counts,
            frequent=frequent,
            count_calls=self.count_calls - calls0,
            candidates_counted=n_cand,
        )


# ---------------------------------------------------------------------------
# Streaming top-k frequent itemsets (served via the delta path)
# ---------------------------------------------------------------------------


@dataclass
class TopKResult:
    """The ``top`` highest-support itemsets of sizes 1..k_max over the
    appended stream, with the support threshold the search settled at."""

    items: list[tuple[Itemset, int]]  # (itemset, exact count), best first
    threshold: int  # smallest min_count tried (all items have count >= it)
    k_max: int
    count_calls: int  # device passes THIS query cost (0 when fully cached)


def topk_itemsets(
    delta: DeltaApriori, k_max: int, top: int, floor: int = 1
) -> TopKResult:
    """Top-``top`` frequent itemsets by support over a DeltaApriori
    stream, without the caller naming a support threshold.

    Threshold search by halving: start at the stream length (only
    universally-supported itemsets qualify) and halve until at least
    ``top`` itemsets are frequent or the ``floor`` is reached.  Each
    probe is a ``DeltaApriori.query``, so repeated probes serve counts
    from the cumulative cache — on a warm state the whole search costs
    zero device passes, which is what makes this a *streaming* query:
    appends are O(|delta|), and the top-k refreshes cheaply after each.

    Deterministic: ties break by (higher count, smaller itemset,
    lexicographic items).  Exactness is inherited from the delta
    contract — every returned count equals the from-scratch count.
    """
    if top < 1:
        raise ValueError(f"top must be >= 1, got {top}")
    if floor < 1:
        raise ValueError(f"floor must be >= 1, got {floor}")
    calls0 = delta.count_calls
    t = max(int(delta.n_tx), floor)
    while True:
        res = delta.query(k_max, t)
        found = [
            (its, res.counts[its])
            for lv in sorted(res.frequent)
            for its in res.frequent[lv]
        ]
        if len(found) >= top or t <= floor:
            break
        t = max(floor, t // 2)
    found.sort(key=lambda ic: (-ic[1], len(ic[0]), ic[0]))
    return TopKResult(
        items=found[:top],
        threshold=t,
        k_max=k_max,
        count_calls=delta.count_calls - calls0,
    )


# ---------------------------------------------------------------------------
# Brute-force oracle (tests)
# ---------------------------------------------------------------------------


def bruteforce_frequent(
    dense_pooled: np.ndarray, k_max: int, min_count: int
) -> dict[Itemset, int]:
    """Exhaustive frequent itemsets of sizes 1..k_max over a pooled dense DB.

    Exponential — tests only.  Uses downward closure for pruning.
    """
    n, m = dense_pooled.shape
    cols = dense_pooled.astype(bool)
    out: dict[Itemset, int] = {}
    level: list[tuple[Itemset, np.ndarray]] = []
    for i in range(m):
        c = int(cols[:, i].sum())
        if c >= min_count:
            out[(i,)] = c
            level.append(((i,), cols[:, i]))
    for _ in range(2, k_max + 1):
        fset = {its for its, _ in level}
        nxt = []
        for cand in apriori_join([its for its, _ in level]):
            mask = np.ones(n, dtype=bool)
            for item in cand:
                mask &= cols[:, item]
            c = int(mask.sum())
            if c >= min_count:
                out[cand] = c
                nxt.append((cand, mask))
        level = nxt
        if not level:
            break
    return out
