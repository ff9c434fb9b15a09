"""Pure-jnp oracle for the grouped matmul over expert-sorted rows, and its
operation and byte counts."""

from __future__ import annotations

import jax.numpy as jnp


def moe_gmm_ref(x: jnp.ndarray, w: jnp.ndarray, tile_expert: jnp.ndarray,
                n_active, tm: int) -> jnp.ndarray:
    """Tile ``i`` of ``x`` times ``w[tile_expert[i]]`` in float32; the rows
    of tiles past ``n_active`` read 0."""
    m, k = x.shape
    tiles = x.reshape(m // tm, tm, k).astype(jnp.float32)
    out = jnp.einsum("itk,ikn->itn", tiles,
                     w[tile_expert].astype(jnp.float32))
    live = (jnp.arange(m // tm) < jnp.reshape(n_active, ()))[:, None, None]
    return jnp.where(live, out, 0.0).reshape(m, -1).astype(x.dtype)


def counts(rows: int, experts: int, k: int, n: int,
           itemsize: int = 2) -> tuple:
    """(flops, bytes) the least a grouped matmul of ``rows`` routed rows
    over ``experts`` touched experts needs: their rows in and out, and each
    touched expert's (k, n) weight once."""
    flops = 2.0 * rows * k * n
    moved = (rows * (k + n) + experts * k * n) * itemsize
    return flops, float(moved)
