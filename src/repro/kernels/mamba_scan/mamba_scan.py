"""Chunked Mamba (S6) selective-scan Pallas TPU kernel.

Grid (B, Dm/bd, T/C) — time innermost (sequential), channel blocks parallel.
The state is held as an (N, bd) plane, channels on the 128 lanes and the
N = 16 state entries on sublanes, so every vector op of the recurrence is
lane-dense (two vregs per 128 channels).  It lives in VMEM scratch across
the chunks of one channel block.  Each chunk first computes ``delta * x``
for its (C, bd) tile at once; the recurrence then runs as a fori_loop over
the chunk's C steps, reading step ``i``'s rows of ``delta`` and
``delta * x`` from VMEM and writing its output row there, so the
sequential loop never touches HBM.  B and C arrive transposed, (N, C) a
chunk with time on the lanes: step ``i``'s (N, 1) column is a masked lane
sum, broadcast across the channel lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import pad_dim, use_interpret

#: time steps a grid step scans (the lane width of its B and C tiles)
CHUNK = 128
#: steps unrolled in each iteration of the chunk's loop
UNROLL = 8


def channel_block(dm: int) -> int:
    """Channels a grid step holds: the widest of 1024, 512, 256, 128
    dividing ``dm``, else all of them.  At Jamba2-3B's 5120 channels and
    32768 steps a block of 1024 took 9.07 ms on a v5e, one of 512 10.45."""
    return next((bd for bd in (1024, 512, 256, 128) if dm % bd == 0), dm)


def _mamba_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref,
                  dtx_ref, yc_ref, *, chunk: int, steps: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    f32 = jnp.float32
    dtx_ref[...] = dt_ref[0] * x_ref[0].astype(f32)          # (C, bd)
    a = a_ref[...]                                           # (N, bd)
    bt = b_ref[0]                                            # (N, C)
    ct = c_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(i, h):
        at = lane == i
        bcol = jnp.sum(jnp.where(at, bt, 0.0), axis=1, keepdims=True)
        ccol = jnp.sum(jnp.where(at, ct, 0.0), axis=1, keepdims=True)
        dt = dt_ref[0, pl.ds(i, 1), :]                       # (1, bd)
        h = jnp.exp(dt * a) * h + bcol * dtx_ref[pl.ds(i, 1), :]
        yc_ref[pl.ds(i, 1), :] = jnp.sum(h * ccol, axis=0, keepdims=True)
        return h

    def steps8(j, h):                     # Mosaic unrolls all or nothing
        for k in range(UNROLL):
            h = step(j * UNROLL + k, h)
        return h

    h = jax.lax.fori_loop(0, chunk // UNROLL, steps8, h_ref[...])
    h_ref[...] = h
    y_ref[0] = yc_ref[...].astype(y_ref.dtype)

    @pl.when(t_idx == steps - 1)
    def _store_state():
        hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba_scan_pallas(x: jax.Array, delta: jax.Array, a: jax.Array,
                      b: jax.Array, c: jax.Array, *, chunk: int = CHUNK):
    """x/delta (B, T, Dm), a (Dm, N), b/c (B, T, N).

    Returns (y (B, T, Dm) — WITHOUT the skip D*x term, added by ops —
    and final state (B, Dm, N) fp32).  T is padded up to a multiple of
    ``chunk`` with ``delta = 0`` steps (exp(0 * A) = 1, no increment), so
    the state after the padding is the state at T.
    """
    bsz, t, dm = x.shape
    n = a.shape[1]
    bd = channel_block(dm)
    f32 = jnp.float32
    xp = pad_dim(x, 1, chunk)
    dp = pad_dim(delta.astype(f32), 1, chunk)
    bp = jnp.swapaxes(pad_dim(b.astype(f32), 1, chunk), 1, 2)   # (B, N, T)
    cp = jnp.swapaxes(pad_dim(c.astype(f32), 1, chunk), 1, 2)
    steps = xp.shape[1] // chunk
    y, h = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=chunk, steps=steps),
        grid=(bsz, dm // bd, steps),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((n, bd), lambda b_, d_, i: (0, d_)),
            pl.BlockSpec((1, n, chunk), lambda b_, d_, i: (b_, 0, i)),
            pl.BlockSpec((1, n, chunk), lambda b_, d_, i: (b_, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((1, n, bd), lambda b_, d_, i: (b_, 0, d_)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, n, dm), f32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bd), f32),
                        pltpu.VMEM((chunk, bd), f32),
                        pltpu.VMEM((chunk, bd), f32)],
        interpret=use_interpret(),
        name="mamba_scan_pallas",
    )(xp, dp, jnp.swapaxes(a.astype(f32), 0, 1), bp, cp)
    return y[:, :t], jnp.swapaxes(h, 1, 2)
