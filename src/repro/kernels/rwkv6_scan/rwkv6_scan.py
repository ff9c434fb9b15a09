"""Chunked RWKV-6 WKV Pallas TPU kernel.

The recurrence is linear in the state, so a chunk of C steps reduces to
matmuls (the chunked linear-attention form), with the (D x D) state carried
across chunks in VMEM scratch — the grid is (B, H, T/C) with the time axis
innermost (sequential on TPU).

Numerical safety: all decay products are expressed relative to the *later*
timestep, i.e. every exponential is exp(negative cumulative log-decay) <= 1,
so nothing overflows regardless of chunk length:

    Lw[t]  = sum_{s<=t} log w_s                     (<= 0, per channel)
    intra  A[t,s] = sum_i r_t[i] k_s[i] e^{Lw[t-1,i] - Lw[s,i]}   (s < t)
    diag   A[t,t] = sum_i r_t[i] u[i] k_t[i]
    y      = A @ v + (r * e^{Lw_prev}) @ S
    S'     = e^{Lw[C-1]} (x) S + sum_s (e^{Lw[C-1] - Lw[s]} * k_s) (x) v_s

The (C, C, D) pairwise-decay tensor stays tiny (C = 32, D = 64 → 512 KiB of
fp32 in VMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


def _cumsum_rows(x):
    """Inclusive prefix sum over axis 0 by log-step shifts (Mosaic has no
    cumsum): after the step with shift ``s`` each row holds the sum of the
    ``2s`` rows ending at it."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < x.shape[0]:
        x = x + jnp.where(rows >= s, pltpu.roll(x, s, 0), 0.0)
        s *= 2
    return x


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_ref,
                  *, chunk: int, steps: int):
    t_idx = pl.program_id(2)

    @pl.when(t_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    r = r_ref[0, 0].astype(f32)          # (C, D)
    k = k_ref[0, 0].astype(f32)
    v = v_ref[0, 0].astype(f32)
    w = w_ref[0, 0].astype(f32)
    u = u_ref[0].astype(f32)             # (1, D)

    lw = _cumsum_rows(jnp.log(w))                    # (C, D), <= 0
    lw_prev = lw - jnp.log(w)                        # exclusive cumsum
    # pairwise decay e^{Lw[t-1] - Lw[s]} for s < t, strictly causal
    diff = lw_prev[:, None, :] - lw[None, :, :]      # (C, C, D)
    ti = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    si = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    strict = ti > si
    decay = jnp.where(strict, jnp.exp(jnp.where(strict, diff, 0.0)), 0.0)
    # strictly-lower triangle of sum_i r[t,i] decay[t,s,i] k[s,i]
    a = jnp.sum(r[:, None, :] * decay * k[None, :, :], axis=-1)
    a_diag = jnp.sum(r * u * k, axis=1, keepdims=True)   # (C, 1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    a = a + a_diag * eye.astype(f32)
    y_intra = jnp.dot(a, v, preferred_element_type=f32)

    s0 = s_ref[...]                                  # (D, D)
    y_state = jnp.dot(r * jnp.exp(lw_prev), s0, preferred_element_type=f32)
    y_ref[0, 0] = (y_intra + y_state).astype(y_ref.dtype)

    lw_last = lw[chunk - 1:chunk]                    # (1, D)
    w_total = jnp.exp(lw_last).reshape(-1, 1)        # (D, 1)
    k_scaled = k * jnp.exp(lw_last - lw)             # (C, D), <= k
    s_ref[...] = w_total * s0 + jnp.dot(
        k_scaled.T, v, preferred_element_type=f32)

    @pl.when(t_idx == steps - 1)
    def _store_state():
        sout_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan_pallas(r: jax.Array, k: jax.Array, v: jax.Array,
                      w: jax.Array, u: jax.Array, *, chunk: int = 32):
    """r/k/v/w (B, H, T, D), u (H, D); T % chunk == 0.

    Returns (y (B, H, T, D), final state (B, H, D, D) fp32).
    """
    b, h, t, d = r.shape
    assert t % chunk == 0, (t, chunk)
    steps = t // chunk
    grid = (b, h, steps)
    y, s = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=chunk, steps=steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            # (1, D) of each head: the last two block dims span the array
            pl.BlockSpec((1, 1, d), lambda b_, h_, i: (h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=use_interpret(),
        name="rwkv6_scan_pallas",
    )(r, k, v, w, u.reshape(h, 1, d))
    return y, s
