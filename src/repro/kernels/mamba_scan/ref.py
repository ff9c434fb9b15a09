"""Pure-jnp oracle for the Mamba (S6) selective scan.

Diagonal state-space recurrence per channel d with state size N:

    h_t = exp(delta_t * A) * h_{t-1} + delta_t * x_t * B_t      (d, N)
    y_t = C_t . h_t + D * x_t                                    (d,)

A (d, N) is the (negative) continuous-time transition, B_t/C_t (N,) are
input-dependent projections, delta_t (d,) the input-dependent step size.
The oracle is the exact sequential lax.scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.machine import WorkCounts


def mamba_scan_ref(x, delta, a, b, c, d, state0=None):
    """x/delta (B, T, Dm), a (Dm, N), b/c (B, T, N), d (Dm,).

    Returns (y (B, T, Dm), final state (B, Dm, N) fp32).
    """
    bsz, t, dm = x.shape
    n = a.shape[1]
    f32 = jnp.float32
    x, delta, b, c = (z.astype(f32) for z in (x, delta, b, c))
    a = a.astype(f32)
    h0 = (jnp.zeros((bsz, dm, n), f32) if state0 is None
          else state0.astype(f32))

    def step(h, xs):
        xt, dt, bt, ct = xs                     # (B,Dm) (B,Dm) (B,N) (B,N)
        da = jnp.exp(dt[..., None] * a[None])   # (B, Dm, N)
        inc = (dt * xt)[..., None] * bt[:, None, :]
        h = da * h + inc
        y = jnp.einsum("bdn,bn->bd", h, ct)
        return h, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(delta, 1, 0),
          jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0))
    h, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x * d[None, None].astype(f32)
    return y.astype(x.dtype), h


def counts(bsz: int, t: int, dm: int, n: int, itemsize: int = 4) -> WorkCounts:
    # per step per channel: exp+mul (2N), increment (2N), readout (2N)
    ops = 6.0 * bsz * t * dm * n
    io = (2.0 * bsz * t * dm + 2.0 * bsz * t * n) * itemsize
    return WorkCounts(ops=ops, dcache_bytes=ops / 3 * itemsize,
                      host_bytes=io, working_set=bsz * dm * n * itemsize)
