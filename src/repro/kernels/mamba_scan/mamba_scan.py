"""Chunked Mamba (S6) selective-scan Pallas TPU kernel.

Grid (B, Dm/bd, T/C) — time innermost (sequential), channel blocks parallel.
The (bd, N) state lives in VMEM scratch across time steps.  Within a chunk
the recurrence runs as a fori_loop of VPU FMAs on the (bd, N) plane; the
chunk's x/delta/B/C tiles are VMEM-resident (the D$-discipline of the
paper), so the sequential loop never touches HBM.

N = 16 keeps the state plane at bd x 16 fp32 = 8 KiB for bd = 128 — the
working set is firmly VMEM-resident and the kernel is bound by the
(B T Dm) x itemsize activation stream, i.e. the memory roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


def _mamba_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref,
                  *, chunk: int, steps: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    f32 = jnp.float32
    x = x_ref[0].astype(f32)             # (C, bd)
    dt = dt_ref[0].astype(f32)           # (C, bd)
    a = a_ref[...].astype(f32)           # (bd, N)
    bmat = b_ref[0].astype(f32)          # (C, N)
    cmat = c_ref[0].astype(f32)          # (C, N)

    def step(i, carry):
        h, y = carry
        da = jnp.exp(dt[i][:, None] * a)                 # (bd, N)
        inc = (dt[i] * x[i])[:, None] * bmat[i][None, :]
        h = da * h + inc
        yt = jnp.sum(h * cmat[i][None, :], axis=1)       # (bd,)
        y = jax.lax.dynamic_update_slice_in_dim(y, yt[None, :], i, axis=0)
        return h, y

    y0 = jnp.zeros((chunk, x.shape[1]), f32)
    h, y = jax.lax.fori_loop(0, chunk, step, (h_ref[...], y0))
    h_ref[...] = h
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(t_idx == steps - 1)
    def _store_state():
        hout_ref[0] = h

@functools.partial(jax.jit, static_argnames=("chunk", "bd"))
def mamba_scan_pallas(x: jax.Array, delta: jax.Array, a: jax.Array,
                      b: jax.Array, c: jax.Array, *, chunk: int = 64,
                      bd: int = 128):
    """x/delta (B, T, Dm), a (Dm, N), b/c (B, T, N).

    Returns (y (B, T, Dm) — WITHOUT the skip D*x term, added by ops —
    and final state (B, Dm, N) fp32).  T % chunk == 0, Dm % bd == 0.
    """
    bsz, t, dm = x.shape
    n = a.shape[1]
    assert t % chunk == 0 and dm % bd == 0, (x.shape, chunk, bd)
    steps = t // chunk
    grid = (bsz, dm // bd, steps)
    y, h = pl.pallas_call(
        functools.partial(_mamba_kernel, chunk=chunk, steps=steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((bd, n), lambda b_, d_, i: (d_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, d_, i: (b_, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, d_, i: (b_, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b_, d_, i: (b_, i, d_)),
            pl.BlockSpec((1, bd, n), lambda b_, d_, i: (b_, d_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, dm), x.dtype),
            jax.ShapeDtypeStruct((bsz, dm, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=use_interpret(),
        name="mamba_scan_pallas",
    )(x, delta, a, b, c)
    return y, h
