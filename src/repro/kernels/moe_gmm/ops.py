"""Public grouped matmul over expert-sorted rows: Pallas on TPU, XLA's
``ragged_dot`` elsewhere.

The row layout is the expert layer's (``repro.models.moe``): the rows of
expert ``e`` occupy ``group_sizes[e]`` rows, a multiple of the row tile,
starting where the previous expert's end; rows past the last group are
unused.  :func:`row_tile` picks the tile from the static row count, so
prefill and decode take different tiles from the same code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import pad_dim, use_interpret
from .moe_gmm import moe_gmm_pallas
from .ref import counts, moe_gmm_ref

__all__ = ["moe_gmm", "row_tile", "tile_plan", "counts", "moe_gmm_ref"]


def row_tile(rows_per_expert: float) -> int:
    """Row tile for an expected ``rows_per_expert``: the next power of two,
    between 16 (a bf16 sublane tile) and 256 (where the MXU's work per
    weight byte passes the v5e's flops-to-bandwidth ratio)."""
    tm = 16
    while tm < 256 and tm < rows_per_expert:
        tm *= 2
    return tm


def _block(dim: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``dim`` and is at most
    ``cap`` (``dim`` itself when it is below 128)."""
    if dim <= 128:
        return dim
    best = 128
    for b in range(128, min(dim, cap) + 1, 128):
        if dim % b == 0:
            best = b
    return best


def tile_plan(group_sizes: jax.Array, rows: int, tm: int):
    """(tile_expert (rows/tm,) int32, n_active (1,) int32) of a layout
    whose groups hold ``group_sizes`` rows each (multiples of ``tm``).
    A tile past the active ones repeats the last active tile's expert."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    n_active = ends[-1] // tm
    tiles = jnp.arange(rows // tm, dtype=jnp.int32)
    expert = jnp.searchsorted(ends, tiles * tm, side="right")
    expert = jnp.minimum(expert, group_sizes.shape[0] - 1).astype(jnp.int32)
    last = expert[jnp.maximum(n_active - 1, 0)]
    expert = jnp.where(tiles < n_active, expert, last)
    return expert, n_active.reshape(1)


@functools.partial(jax.jit, static_argnames=("tm", "impl"))
def moe_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
            layer: jax.Array | int = 0, *, tm: int,
            impl: str = "auto") -> jax.Array:
    """x (M, K) rows in the layout above, w (E, K, N) or (L, E, K, N)
    stacked over layers with ``layer`` picking one, group_sizes (E,) ->
    (M, N) in ``x.dtype``, accumulated in float32.  Rows past the last
    group are undefined on the Pallas path (zero on the XLA path).

    impl: "auto" (pallas on TPU, xla otherwise), "pallas", "xla".
    """
    if impl == "auto":
        impl = "xla" if use_interpret() else "pallas"
    if w.ndim == 3:
        w = w[None]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if impl == "xla":
        return jax.lax.ragged_dot(
            x, w[layer[0]], group_sizes.astype(jnp.int32),
            preferred_element_type=jnp.float32).astype(x.dtype)
    m, k = x.shape
    n = w.shape[3]
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    tile_expert, n_active = tile_plan(group_sizes, m, tm)
    tk, tn = _block(k, 1024), _block(n, 1536)
    xp = pad_dim(x, 1, tk)
    wp = pad_dim(pad_dim(w, 2, tk), 3, tn)
    out = moe_gmm_pallas(xp, wp, tile_expert, n_active, layer, tm=tm, tk=tk,
                         tn=tn)
    return out[:, :n]
