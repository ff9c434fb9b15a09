"""Branch-free peak/trough delineation Pallas kernel.

Each grid step (work-group) flags one block of samples; the predicate needs
x[i-1] and x[i+1], so the kernel receives three BlockSpec views of the same
input — previous, current and next block (index maps clamp at the edges).
Every lane evaluates *both* the peak and the trough predicates and selects
with a mask: that is the TPU rendering of the e-GPU's SIMT thread masking
for divergent branches (§VIII-C), made explicit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import use_interpret


def _delineate_kernel(xp_ref, xc_ref, xn_ref, o_ref, *, block: int, n: int,
                      thr, blocks: int):
    i = pl.program_id(0)
    xp = xp_ref[...]
    xc = xc_ref[...]
    xn = xn_ref[...]
    # previous sample of lane j: window[j + block - 1] over [prev | cur]
    wprev = jnp.concatenate([xp, xc], axis=1)
    prev = jax.lax.slice_in_dim(wprev, block - 1, 2 * block - 1, axis=1)
    # first block has no real predecessor: clamp to x[0]
    prev = jnp.where((i == 0), jnp.concatenate([xc[:, :1], xc[:, :-1]], axis=1),
                     prev)
    wnext = jnp.concatenate([xc, xn], axis=1)
    nxt = jax.lax.slice_in_dim(wnext, 1, block + 1, axis=1)
    nxt = jnp.where((i == blocks - 1),
                    jnp.concatenate([xc[:, 1:], xc[:, -1:]], axis=1), nxt)

    gid = i * block + jax.lax.broadcasted_iota(jnp.int32, xc.shape, 1)
    interior = (gid > 0) & (gid < n - 1)
    t = jnp.asarray(thr, xc.dtype)
    is_peak = (xc > prev) & (xc >= nxt) & (xc > t) & interior
    is_trough = (xc < prev) & (xc <= nxt) & (xc < -t) & interior
    # Mosaic has no int8 subtract: subtract in int32, narrow on the store
    o_ref[...] = (is_peak.astype(jnp.int32)
                  - is_trough.astype(jnp.int32)).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block", "thr", "true_n"))
def delineate_pallas(x: jax.Array, thr, *, block: int = 512,
                     true_n: int | None = None) -> jax.Array:
    """Flags (+1 peak / -1 trough / 0) for a 1-D signal; ``len(x)`` must be a
    multiple of ``block`` (ops.delineate pads and crops).  ``thr`` is a
    compile-time scalar (it lands in the kernel as an immediate); ``true_n``
    is the unpadded length (endpoints are never extrema)."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    blocks = n // block
    x2 = x.reshape(1, n)
    true_n = n if true_n is None else true_n
    return pl.pallas_call(
        functools.partial(_delineate_kernel, block=block, n=true_n, thr=thr,
                          blocks=blocks),
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, jnp.maximum(i - 1, 0))),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, jnp.minimum(i + 1, blocks - 1))),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int8),
        interpret=use_interpret(),
        name="delineate_pallas",
    )(x2, x2, x2)[0]
