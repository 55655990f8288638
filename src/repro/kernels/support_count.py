"""Pallas TPU kernel: itemset support counting over packed bitmaps.

The compute hot-spot of the paper's frequent-itemset algorithms: for every
candidate mask m and transaction t, hit = AND_w((t_w & m_w) == m_w);
support(m) = Σ_t hit.

Layout is transposed for TPU lane tiling: transactions arrive as (W, N)
int32 and candidates as (W, C) int32 so the *vector* dimensions (N, C) sit
on the 128-wide lane axis and W (≤ 32 words = 1024 items) is a small
static leading loop.  Each program materialises a (TN, TC) hit block on
the VPU and reduces it into a (1, TC) partial; the grid is (C tiles, N
tiles) with N innermost so the output block accumulates sequentially
(TPU grid order guarantees the revisiting program sees its prior value).

Outputs are lane-dense (1, C) rows written in (1, TC) blocks.  Mosaic
refuses a 1-D block narrower than its array, and ``jax.vmap`` (the
``ops.*_sites`` forms) turns a 1-D block into a (1, TC) block of an
(S, C) array, which breaks the (8, 128) tiling rule; a (1, TC) block of
a (1, C) row, or of its vmapped (S, 1, C) form, does not.  The prune
kernel's two scalars ride as one (1, 2) row for the same reason.

VMEM per program: W·(TN + TC)·4 B + TN·TC·4 B + 2·TC·4 B for the output
rows — at the largest lattice point, W=32, TN=TC=1024: ≈ 4.5 MB ≪ 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pad_to


def _kernel(tx_ref, mask_ref, out_ref):
    w = tx_ref.shape[0]
    tx = tx_ref[...]  # (W, TN) int32
    mk = mask_ref[...]  # (W, TC) int32
    hit = jnp.ones((tx.shape[1], mk.shape[1]), dtype=jnp.bool_)  # (TN, TC)
    for ww in range(w):  # static, small
        t = tx[ww][:, None]  # (TN, 1)
        m = mk[ww][None, :]  # (1, TC)
        hit &= (t & m) == m
    partial = jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)  # (1, TC)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def support_count_pallas(
    tx_t: jax.Array,  # (W, N) int32 — transposed packed transactions
    masks_t: jax.Array,  # (W, C) int32 — transposed packed candidate masks
    block_n: int = 512,
    block_c: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Support counts for arbitrary (N, C): inputs are auto-padded to the
    block multiples.  Padded transactions are all-zero words, which match
    no non-empty mask; an all-zero (empty-itemset) mask WOULD match them,
    so its count is corrected by the pad row count after the kernel —
    padded rows therefore contribute zero support to every candidate.
    Padded candidate columns are sliced away before returning.  Block-
    multiple inputs take the original zero-copy path bit-for-bit.

    Zero-size fast paths: C=0 candidates (a dried-up Apriori level) or
    N=0 transactions (an empty delta batch) return without building a
    degenerate Pallas grid — every support over zero transactions is
    zero, and zero candidates have zero counts."""
    w, n = tx_t.shape
    w2, c = masks_t.shape
    assert w == w2, f"word-width mismatch: transactions {w} vs masks {w2}"
    if c == 0 or n == 0:
        return jnp.zeros((c,), jnp.int32)
    np_ = pad_to(max(n, block_n), block_n)
    cp_ = pad_to(max(c, block_c), block_c)
    tx_p = tx_t if np_ == n else jnp.zeros((w, np_), tx_t.dtype).at[:, :n].set(tx_t)
    mk_p = masks_t if cp_ == c else jnp.zeros((w, cp_), masks_t.dtype).at[:, :c].set(masks_t)
    grid = (cp_ // block_c, np_ // block_n)  # N innermost → sequential accumulation
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((w, block_c), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, cp_), jnp.int32),
        interpret=interpret,
    )(tx_p, mk_p)[0, :c]
    if np_ != n:
        empty_mask = jnp.all(masks_t == 0, axis=0)  # matches the zero pad rows
        out = out - jnp.where(empty_mask, jnp.int32(np_ - n), jnp.int32(0))
    return out


def _prune_kernel(tx_ref, mask_ref, par_ref, out_ref, freq_ref):
    """``_kernel`` plus the level-hygiene step fused in: on the LAST
    transaction tile each candidate block corrects its own pad-row
    overcount (all-zero masks match the zero pad rows; ``par_ref[0]``
    carries the pad-row count) and emits the ``count >= min_count``
    frequent flag (``par_ref[1]``) next to the final count; ``par_ref``
    is one (1, 2) row so its block is the whole array, with or without
    the vmapped site axis — one device
    pass returns both, so the level loop thresholds without a host
    round-trip of the raw count vector."""
    w = tx_ref.shape[0]
    tx = tx_ref[...]  # (W, TN) int32
    mk = mask_ref[...]  # (W, TC) int32
    hit = jnp.ones((tx.shape[1], mk.shape[1]), dtype=jnp.bool_)  # (TN, TC)
    for ww in range(w):  # static, small
        t = tx[ww][:, None]
        m = mk[ww][None, :]
        hit &= (t & m) == m
    partial = jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)  # (1, TC)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        out_ref[...] += partial

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finalize():
        empty = jnp.ones((1, mk.shape[1]), dtype=jnp.bool_)
        for ww in range(w):
            empty &= mk[ww : ww + 1] == 0
        corrected = out_ref[...] - jnp.where(empty, par_ref[0, 0], jnp.int32(0))
        out_ref[...] = corrected
        freq_ref[...] = (corrected >= par_ref[0, 1]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def support_count_prune_pallas(
    tx_t: jax.Array,  # (W, N) int32 — transposed packed transactions
    masks_t: jax.Array,  # (W, C) int32 — transposed packed candidate masks
    min_count: jax.Array | int,  # scalar int — the frequency threshold
    block_n: int = 512,
    block_c: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused count-then-threshold: returns ``(counts (C,) int32,
    frequent (C,) bool)`` where ``frequent == counts >= min_count``
    exactly — the Apriori level's candidate-hygiene step folded into the
    counting pass.  Same padding contract as :func:`support_count_pallas`
    (including the empty-mask pad correction, here applied IN-kernel so
    the emitted flags see corrected counts); ``min_count`` is a traced
    scalar, so distinct thresholds share one compilation per block
    config.  Zero-size fast paths mirror the plain kernel's."""
    w, n = tx_t.shape
    w2, c = masks_t.shape
    assert w == w2, f"word-width mismatch: transactions {w} vs masks {w2}"
    mc = jnp.asarray(min_count, jnp.int32)
    if c == 0 or n == 0:
        counts = jnp.zeros((c,), jnp.int32)
        return counts, counts >= mc
    np_ = pad_to(max(n, block_n), block_n)
    cp_ = pad_to(max(c, block_c), block_c)
    tx_p = tx_t if np_ == n else jnp.zeros((w, np_), tx_t.dtype).at[:, :n].set(tx_t)
    mk_p = masks_t if cp_ == c else jnp.zeros((w, cp_), masks_t.dtype).at[:, :c].set(masks_t)
    params = jnp.stack([jnp.full((), np_ - n, jnp.int32), mc])[None, :]  # (1, 2)
    grid = (cp_ // block_c, np_ // block_n)  # N innermost → sequential accumulation
    counts, freq = pl.pallas_call(
        _prune_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((w, block_c), lambda i, j: (0, i)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_c), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, cp_), jnp.int32),
            jax.ShapeDtypeStruct((1, cp_), jnp.int32),
        ],
        interpret=interpret,
    )(tx_p, mk_p, params)
    return counts[0, :c], freq[0, :c].astype(jnp.bool_)
