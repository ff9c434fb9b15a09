"""FIR filter Pallas kernel with halo blocks.

Work decomposition follows the Tiny-OpenCL NDRange: each grid step (work-
group) produces one block of outputs.  The causal window needs ``taps - 1``
samples of history, so the kernel receives the *previous* block as a second
BlockSpec view of the same input (index map ``max(i-1, 0)``) — the TPU
version of the paper's observation that FIR's sequential accesses coalesce
perfectly (§VIII-C): every sample is DMA'd into VMEM exactly once per block
role, and the taps loop runs from VMEM/registers.

The taps loop is unrolled statically (taps is a compile-time constant), so
each iteration is a shifted static slice — the VPU analogue of the e-GPU's
register sliding window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import use_interpret


def _fir_kernel(x_prev_ref, x_cur_ref, h_ref, o_ref, *, taps: int, block: int,
                fxp_shift: int | None):
    i = pl.program_id(0)
    # (1, block) layout: TPU wants >=2-D; lane dim = block
    prev = x_prev_ref[...]
    cur = x_cur_ref[...]
    # zero history for the first block (index map clamps i-1 to 0)
    prev = jnp.where(i == 0, jnp.zeros_like(prev), prev)
    w = jnp.concatenate([prev, cur], axis=-1)      # (1, 2*block)
    acc = jnp.zeros(cur.shape, jnp.int32 if fxp_shift is not None else jnp.float32)
    for t in range(taps):
        # y[j] += h[t] * x[j - t]  ->  w[block + j - t]
        sl = jax.lax.slice_in_dim(w, block - t, 2 * block - t, axis=1)
        acc = acc + h_ref[0, t] * sl.astype(acc.dtype)
    if fxp_shift is not None:
        acc = jnp.right_shift(acc, fxp_shift)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "fxp_shift"))
def fir_pallas(x: jax.Array, h: jax.Array, *, block: int = 512,
               fxp_shift: int | None = None) -> jax.Array:
    """Causal FIR via Pallas.  ``x`` length must be a multiple of ``block``
    and ``block >= taps`` (ops.fir pads & validates)."""
    n = x.shape[0]
    taps = h.shape[0]
    assert n % block == 0 and block >= taps, (n, block, taps)
    x2 = x.reshape(1, n)
    h2 = h.reshape(1, taps)
    grid = (n // block,)
    out = pl.pallas_call(
        functools.partial(_fir_kernel, taps=taps, block=block, fxp_shift=fxp_shift),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, jnp.maximum(i - 1, 0))),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, taps), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype if fxp_shift is not None
                                       else jnp.float32),
        interpret=use_interpret(),
        name="fir_pallas",
    )(x2, x2, h2)
    return out[0]
