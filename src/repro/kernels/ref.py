"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels are validated against them with
``interpret=True`` across shape/dtype sweeps (see tests/test_kernels_*).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kmeans_assign_ref(x: jax.Array, centers: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x (N, D), centers (K, D) -> (assign (N,) int32, min_d2 (N,) f32)."""
    x = x.astype(jnp.float32)
    c = centers.astype(jnp.float32)
    d2 = (
        jnp.sum(x**2, axis=-1)[:, None]
        + jnp.sum(c**2, axis=-1)[None, :]
        - 2.0 * jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    )
    d2 = jnp.maximum(d2, 0.0)
    return jnp.argmin(d2, axis=-1).astype(jnp.int32), jnp.min(d2, axis=-1)


def support_count_ref(tx: jax.Array, masks: jax.Array) -> jax.Array:
    """tx (N, W) uint32/int32, masks (C, W) -> (C,) int32 supports."""
    tx = tx.astype(jnp.uint32)
    masks = masks.astype(jnp.uint32)
    hit = (tx[:, None, :] & masks[None, :, :]) == masks[None, :, :]  # (N, C, W)
    return jnp.sum(jnp.all(hit, axis=-1), axis=0).astype(jnp.int32)
