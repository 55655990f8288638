"""Jitted public wrappers around the Pallas kernels.

Handle padding/layout so callers pass natural shapes.  On a TPU backend
every kernel is compiled by Mosaic (``chip_smoke.py`` asserts that each
compiled program holds a ``tpu_custom_call``, and
``tests/test_tpu_compile.py`` compiles them for a described v5e); on any
other backend the same kernels run in Pallas interpret mode, which is
how the CPU test suite checks them against ``ref``.

Block-size seam: every mining-kernel wrapper takes ``block=`` —

  * ``None`` (default) — the module's default mode: the shipped
    hard-coded blocks, unless the mode was flipped to ``"auto"`` via
    :func:`set_default_block` or ``REPRO_KERNEL_BLOCKS=auto``;
  * ``"auto"`` — consult :mod:`repro.kernels.autotune`: the memoized
    winner for this padded shape, searching (and memoizing) on first
    sight.  Under a jit trace timing is impossible, so traced calls use
    the memoized winner when one exists and the defaults otherwise;
  * an explicit config — ``(block_n, block_c)`` for support counting,
    ``block_n`` for k-means assignment — used as-is (the legacy
    ``block_n=``/``block_c=`` kwargs still work and win over ``block=``).

Block size never changes results (each kernel's padding contract), so
the seam changes speed and nothing else — ``core.apriori`` and the
batched/multihost backends pick up tuned blocks with zero call-site
churn.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import autotune, pad_to, ref
from repro.kernels.kmeans_assign import BIG, kmeans_assign_pallas
from repro.kernels.support_count import (
    support_count_pallas,
    support_count_prune_pallas,
)

_BLOCK_MODE = (
    "auto" if os.environ.get("REPRO_KERNEL_BLOCKS", "default") == "auto" else "default"
)


def set_default_block(mode: str) -> str:
    """Flip the module-wide block mode (``"default"`` | ``"auto"``);
    returns the previous mode.  ``"auto"`` makes every wrapper call with
    ``block=None`` consult the autotuner — activate it process-wide to
    run tuned blocks with zero call-site churn."""
    global _BLOCK_MODE
    if mode not in ("default", "auto"):
        raise ValueError(f"unknown block mode {mode!r} (want 'default' or 'auto')")
    prev = _BLOCK_MODE
    _BLOCK_MODE = mode
    return prev


def default_block() -> str:
    """The current module-wide block mode."""
    return _BLOCK_MODE


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _resolve_support_blocks(
    tx_t, masks_t, block, block_n, block_c, interpret: bool
) -> tuple[int, int]:
    """The (block_n, block_c) one support-count dispatch will use.
    Explicit kwargs win; then an explicit ``block`` tuple; then the
    autotuner when auto is requested (lookup-only under a trace); else
    the shipped defaults."""
    dn, dc = autotune.DEFAULT_SUPPORT_BLOCKS
    if block_n is not None or block_c is not None:
        return (block_n or dn, block_c or dc)
    if isinstance(block, tuple):
        return block
    auto = block == "auto" or (block is None and _BLOCK_MODE == "auto")
    if not auto:
        return (dn, dc)
    w, n = tx_t.shape
    _, c = masks_t.shape
    if _is_tracer(tx_t) or _is_tracer(masks_t):
        cfg = autotune.lookup(autotune.support_count_key(w, n, c, tx_t.dtype, interpret))
        return cfg if cfg is not None else (dn, dc)
    return tuple(autotune.tune_support_count(tx_t, masks_t, interpret=interpret)["config"])


def _resolve_kmeans_block(xp, cp, block, block_n, interpret: bool) -> int:
    """The block_n one kmeans-assign dispatch will use (same resolution
    order as :func:`_resolve_support_blocks`)."""
    if block_n is not None:
        return block_n
    if isinstance(block, int):
        return block
    auto = block == "auto" or (block is None and _BLOCK_MODE == "auto")
    if not auto:
        return autotune.DEFAULT_KMEANS_BLOCK
    n, d = xp.shape
    k, _ = cp.shape
    if _is_tracer(xp) or _is_tracer(cp):
        cfg = autotune.lookup(autotune.kmeans_assign_key(n, d, k, xp.dtype, interpret))
        return cfg if cfg is not None else autotune.DEFAULT_KMEANS_BLOCK
    return autotune.tune_kmeans_assign(xp, cp, interpret=interpret)["config"]


def kmeans_assign(
    x: jax.Array,
    centers: jax.Array,
    block: int | str | None = None,
    block_n: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-center assignment.  x (N, D), centers (K, D) ->
    (assign (N,) int32, min_d2 (N,) f32).  Pads D and K to the 128-lane
    boundary per the kernel contract (the kernel auto-pads N itself)."""
    n, d = x.shape
    k, _ = centers.shape
    dp = pad_to(max(d, 128), 128)
    kp = pad_to(max(k, 128), 128)
    xp = jnp.zeros((n, dp), jnp.float32).at[:, :d].set(x.astype(jnp.float32))
    # padded center rows sit at +BIG so they never win the argmin;
    # padded D columns are zero in both operands (distance unchanged)
    cp = jnp.full((kp, dp), 0.0, jnp.float32)
    cp = cp.at[:, :d].set(jnp.full((kp, d), BIG, jnp.float32))
    cp = cp.at[:k, :d].set(centers.astype(jnp.float32))
    interp = not _on_tpu()
    bn = _resolve_kmeans_block(xp, cp, block, block_n, interp)
    return kmeans_assign_pallas(xp, cp, block_n=bn, interpret=interp)


def _to_kernel_layout(tx_packed: jax.Array, masks: jax.Array):
    """(N, W)/(C, W) uint32 -> the kernel's transposed (W, ·) int32."""
    tx_t = jax.lax.bitcast_convert_type(tx_packed.astype(jnp.uint32), jnp.int32).T
    mk_t = jax.lax.bitcast_convert_type(masks.astype(jnp.uint32), jnp.int32).T
    return tx_t, mk_t


def support_count(
    tx_packed: jax.Array,
    masks: jax.Array,
    block: tuple[int, int] | str | None = None,
    block_n: int | None = None,
    block_c: int | None = None,
) -> jax.Array:
    """Support counts.  tx_packed (N, W) uint32, masks (C, W) uint32 ->
    (C,) int32.  Transposes to the kernel's (W, ·) lane layout; the
    kernel auto-pads N/C to its blocks (padded transactions count zero
    support, padded candidate outputs are sliced away)."""
    n, w = tx_packed.shape
    c, w2 = masks.shape
    assert w == w2
    tx_t, mk_t = _to_kernel_layout(tx_packed, masks)
    interp = not _on_tpu()
    bn, bc = _resolve_support_blocks(tx_t, mk_t, block, block_n, block_c, interp)
    return support_count_pallas(tx_t, mk_t, block_n=bn, block_c=bc, interpret=interp)


def support_count_prune(
    tx_packed: jax.Array,
    masks: jax.Array,
    min_count,
    block: tuple[int, int] | str | None = None,
    block_n: int | None = None,
    block_c: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused count + threshold: returns ``(counts (C,) int32, frequent
    (C,) bool)`` with ``frequent == counts >= min_count`` exactly — the
    Apriori level's candidate-hygiene step in ONE device pass, so the
    level loop reads back the (tiny) frequent mask instead of
    thresholding the raw count vector on host.  ``min_count`` is traced:
    distinct thresholds share one compilation.  Tuned blocks are shared
    with :func:`support_count` — the compute loop is identical, so one
    search serves both."""
    n, w = tx_packed.shape
    c, w2 = masks.shape
    assert w == w2
    tx_t, mk_t = _to_kernel_layout(tx_packed, masks)
    interp = not _on_tpu()
    bn, bc = _resolve_support_blocks(tx_t, mk_t, block, block_n, block_c, interp)
    return support_count_prune_pallas(
        tx_t, mk_t, min_count, block_n=bn, block_c=bc, interpret=interp
    )


def flash_attention(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Skv, Kv, Dh)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    cap: float = 0.0,
    block_q: int = 128,
    block_k: int = 256,
) -> jax.Array:
    """Flash attention with GQA; returns (B, Sq, H, Dh).

    Flattens (batch, heads) into the kernel's leading grid dim; the KV
    index map folds the GQA group so K/V are never repeated.  Pads Sq/Skv
    to the block sizes (padded keys sit behind an out-of-range causal/pad
    mask because padded q/k positions extend past the real length and the
    kernel's positional mask plus the final slice discard them)."""
    from repro.kernels.flash_attention import flash_attention_pallas

    b, sq, h, dh = q.shape
    _, skv, kvh, _ = k.shape
    tq = min(block_q, pad_to(sq, 8))
    tk = min(block_k, pad_to(skv, 8))
    sqp, skp = pad_to(sq, tq), pad_to(skv, tk)
    # padded keys are masked by causality (k_pos >= skv > any real q_pos);
    # without causality there is no mask to hide them
    assert causal or skp == skv, "non-causal flash requires Skv % block_k == 0"
    qf = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, skp - skv), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, skp - skv), (0, 0), (0, 0)))
    # (B, S, H, D) -> (B*H, S, D) with heads grouped per batch
    qf = qf.transpose(0, 2, 1, 3).reshape(b * h, sqp, dh)
    kf = kf.transpose(0, 2, 1, 3).reshape(b * kvh, skp, dh)
    vf = vf.transpose(0, 2, 1, 3).reshape(b * kvh, skp, dh)
    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, window=window, cap=cap,
        block_q=tq, block_k=tk, interpret=not _on_tpu(),
    )
    out = out.reshape(b, h, sqp, dh).transpose(0, 2, 1, 3)
    return out[:, :sq]


def slstm_scan(wx: jax.Array, r: jax.Array, bias: jax.Array, state0, t_chunk: int = 16):
    """sLSTM sequence scan with VMEM-resident recurrent weights.

    wx (B, S, H, 4P) batch-major; state0 = (c, n, hid) each (B, H, P).
    Returns (hids (B, S, H, P), (cT, nT, hT)).  Pads S to the time-chunk
    (identity steps would corrupt state, so padding uses zero wx and the
    final state is captured from the real tail by re-running the remainder
    — instead we simply require S % t_chunk == 0 by choosing a divisor)."""
    from repro.kernels.slstm_cell import slstm_scan_pallas

    b, s, h, p4 = wx.shape
    tc = t_chunk
    while s % tc:
        tc //= 2
    tc = max(tc, 1)
    c0, n0, h0 = state0
    hids, cT, nT, hT = slstm_scan_pallas(
        jnp.moveaxis(wx, 1, 0), r, bias, c0, n0, h0, t_chunk=tc, interpret=not _on_tpu()
    )
    return jnp.moveaxis(hids, 0, 1), (cT, nT, hT)


def support_count_sites(
    tx_packed_s: jax.Array,
    masks_s: jax.Array,
    block: tuple[int, int] | str | None = None,
) -> jax.Array:
    """Fused site-axis support counting: ONE dispatch for S sites.

    tx_packed_s (S, N, W) uint32, masks_s (S, C, W) uint32 -> (S, C)
    int32 — the vmapped form of :func:`support_count` (vmap lifts the
    Pallas grid by one site dimension, so the whole fan-out runs as a
    single kernel launch instead of S host-loop dispatches).  Per-site
    padding semantics are unchanged.  The block config is resolved ONCE
    from the shared per-site shape BEFORE the vmap (autotuning times
    site 0's slice on a cache miss), so the fused dispatch runs tuned
    blocks too.
    """
    blk = _sites_support_blocks(tx_packed_s, masks_s, block)
    return jax.vmap(lambda t, m: support_count(t, m, block=blk))(tx_packed_s, masks_s)


def _sites_support_blocks(tx_packed_s, masks_s, block) -> tuple[int, int]:
    """Resolve the per-site support-count blocks for a fused site-axis
    dispatch: every site shares one padded shape, so site 0's slice
    stands in for all of them (tracers fall back to lookup/defaults
    inside :func:`_resolve_support_blocks`)."""
    if isinstance(block, tuple):
        return block
    tx_t, mk_t = _to_kernel_layout(tx_packed_s[0], masks_s[0])
    return _resolve_support_blocks(tx_t, mk_t, block, None, None, not _on_tpu())


def support_count_prune_sites(
    tx_packed_s: jax.Array,
    masks_s: jax.Array,
    min_counts: jax.Array,
    block: tuple[int, int] | str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused site-axis count + threshold: ONE dispatch for S sites with
    PER-SITE thresholds.  tx_packed_s (S, N, W), masks_s (S, C, W),
    min_counts (S,) int32 -> (counts (S, C) int32, frequent (S, C)
    bool) — the vmapped form of :func:`support_count_prune` (the
    threshold is a mapped operand, so heterogeneous per-site minimum
    supports ride the same fused launch)."""
    blk = _sites_support_blocks(tx_packed_s, masks_s, block)
    mc = jnp.asarray(min_counts, jnp.int32)
    return jax.vmap(lambda t, m, c: support_count_prune(t, m, c, block=blk))(
        tx_packed_s, masks_s, mc
    )


def kmeans_assign_sites(
    xs: jax.Array,
    centers_s: jax.Array,
    block: int | str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused site-axis K-Means assignment: ONE dispatch for S sites.

    xs (S, N, D), centers_s (S, K, D) -> (assign (S, N) int32,
    min_d2 (S, N) f32) — the vmapped form of :func:`kmeans_assign`.
    Like :func:`support_count_sites`, the block config resolves once
    from the shared per-site shape before the vmap.
    """
    blk = block
    if not isinstance(blk, int):
        # resolve from site 0's padded shape (lane-pad D/K as the
        # per-site wrapper will, so the memo key matches)
        n, d = xs.shape[1], xs.shape[2]
        k = centers_s.shape[1]
        dp = pad_to(max(d, 128), 128)
        kp = pad_to(max(k, 128), 128)
        if _is_tracer(xs) or _is_tracer(centers_s):
            interp = not _on_tpu()
            auto = blk == "auto" or (blk is None and _BLOCK_MODE == "auto")
            cfg = (
                autotune.lookup(autotune.kmeans_assign_key(n, dp, kp, jnp.float32, interp))
                if auto
                else None
            )
            blk = cfg if cfg is not None else autotune.DEFAULT_KMEANS_BLOCK
        else:
            xp = jnp.zeros((n, dp), jnp.float32).at[:, :d].set(xs[0].astype(jnp.float32))
            cp = jnp.full((kp, dp), 0.0, jnp.float32)
            cp = cp.at[:, :d].set(jnp.full((kp, d), BIG, jnp.float32))
            cp = cp.at[:k, :d].set(centers_s[0].astype(jnp.float32))
            blk = _resolve_kmeans_block(xp, cp, block, None, not _on_tpu())
    return jax.vmap(lambda x, c: kmeans_assign(x, c, block=blk))(xs, centers_s)


# re-export oracles for convenience
kmeans_assign_ref = ref.kmeans_assign_ref
support_count_ref = ref.support_count_ref
