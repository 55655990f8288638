"""Pallas TPU kernel: K-Means assignment (pairwise sq-distance + argmin).

The hot inner loop of the paper's per-site local clustering.  TPU-native
formulation: d²(x,c) = ‖x‖² + ‖c‖² − 2·x·cᵀ so the dominant term is a
(TN×D)·(D×K) matmul that runs on the MXU; the argmin/min run on the VPU.

Tiling: grid over N tiles.  Each program holds one (TN, D) block of points
and the full (K, D) center set in VMEM (K and D are padded to the 128-lane
boundary by ``ops.kmeans_assign``).  VMEM footprint per program:
TN·D + K·D + TN·K floats — e.g. TN=1024, D=128, K=128: ~1.1 MB ≪ 16 MB.
The (N,) results are written as lane-dense (1, N) rows in (1, TN) blocks:
a 1-D block narrower than its array, or the (1, TN) block that
``jax.vmap`` makes of one, is refused by Mosaic's (8, 128) tiling rule,
while a (1, TN) block of a (1, N) or vmapped (S, 1, N) array is not.
The matmul runs at ``Precision.HIGHEST`` (f32 contraction on the MXU), the
precision ``core.stats.pairwise_sq_dists`` and ``ref.kmeans_assign_ref``
use, so the kernel and the jnp path rank centres alike on the chip.

Padding contract (enforced by the wrapper): padded D columns are zero in
both x and centers (distances unchanged); padded K rows carry +BIG
sentinel centers so they never win the argmin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pad_to

BIG = 1e30  # sentinel coordinate for padded center rows


def _kernel(x_ref, c_ref, assign_ref, mind2_ref):
    x = x_ref[...].astype(jnp.float32)  # (TN, D)
    c = c_ref[...].astype(jnp.float32)  # (K, D)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)  # (TN, 1)
    c2 = jnp.sum(c * c, axis=-1)[None, :]  # (1, K)
    # MXU: (TN, D) @ (D, K)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (TN, K)
    d2 = x2 + c2 - 2.0 * xc
    # (TN,) per-point results stored as one lane-dense (1, TN) row
    assign_ref[...] = jnp.argmin(d2, axis=-1).astype(jnp.int32)[None, :]
    mind2_ref[...] = jnp.maximum(jnp.min(d2, axis=-1), 0.0)[None, :]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(
    x: jax.Array,  # (N, D) f32 — any N (auto-padded to block_n), D % 128 == 0
    centers: jax.Array,  # (K, D) f32 — K % 128 == 0, padded rows = BIG
    block_n: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Assignment for arbitrary N: points are auto-padded to the block
    multiple with zero rows whose outputs are sliced away before
    returning (padded rows cost compute, never correctness).  Block-
    multiple inputs take the original zero-copy path bit-for-bit.  The
    D/K lane-padding contract (zero columns, +BIG sentinel center rows)
    remains the wrapper's job — see ``ops.kmeans_assign``.

    Zero-size fast path: N=0 points (an empty delta batch) returns empty
    outputs without building a degenerate Pallas grid."""
    n, d = x.shape
    k, d2_ = centers.shape
    assert d == d2_, (x.shape, centers.shape)
    if n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
    np_ = pad_to(max(n, block_n), block_n)
    x_p = x if np_ == n else jnp.zeros((np_, d), x.dtype).at[:n].set(x)
    grid = (np_ // block_n,)
    assign, mind2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, np_), jnp.int32),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
        ],
        interpret=interpret,
    )(x_p, centers)
    return assign[0, :n], mind2[0, :n]
