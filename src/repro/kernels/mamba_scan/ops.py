"""Public Mamba selective-scan op: Pallas on TPU, chunked assoc-scan on XLA.

The XLA path runs a lax.scan over time chunks carrying the (B, Dm, N)
state; within each chunk a jax.lax.associative_scan (O(log C) depth)
expands the linear recurrence.  Live memory is O(B * C * Dm * N) per chunk
instead of O(B * T * Dm * N) — the same VMEM-bounded discipline as the
Pallas kernel, so the dry-run's memory_analysis reflects the real design.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.device import EGPU_16T, EGPUConfig
from ...core.program import kernel_family
from ...core.runtime import Kernel
from ..common import pad_dim, use_interpret
from .mamba_scan import mamba_scan_pallas
from .ref import counts, mamba_scan_ref

__all__ = ["mamba_scan", "counts", "mamba_scan_ref", "build_kernel"]


def _combine(p, q):
    (pa, pb), (qa, qb) = p, q
    return pa * qa, qb + qa * pb


def _chunked_assoc(x, delta, a, b, c, state0, chunk):
    """lax.scan over chunks; associative scan inside each chunk."""
    f32 = jnp.float32
    bsz, t, dm = x.shape
    n = a.shape[1]
    nc = t // chunk
    a32 = a.astype(f32)
    h0 = (jnp.zeros((bsz, dm, n), f32) if state0 is None
          else state0.astype(f32))

    def body(h, xs):
        xc, dtc, bc, cc = xs                                 # (B, C, ...)
        xc, dtc, bc, cc = (z.astype(f32) for z in (xc, dtc, bc, cc))
        da = jnp.exp(dtc[..., None] * a32[None, None])       # (B, C, Dm, N)
        inc = (dtc * xc)[..., None] * bc[:, :, None, :]
        inc = inc.at[:, 0].add(da[:, 0] * h)                 # fold carry in
        _, hc = jax.lax.associative_scan(_combine, (da, inc), axis=1)
        y = jnp.einsum("btdn,btn->btd", hc, cc)
        return hc[:, -1], y

    def split(z):
        return jnp.moveaxis(z.reshape(bsz, nc, chunk, *z.shape[2:]), 1, 0)

    h, ys = jax.lax.scan(body, h0, (split(x), split(delta), split(b), split(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, t, dm)
    return y, h


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def mamba_scan(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, d: jax.Array, state0: jax.Array | None = None,
               *, chunk: int = 64, impl: str = "auto"):
    """Selective scan: x/delta (B,T,Dm), a (Dm,N), b/c (B,T,N), d (Dm,).

    Returns (y (B,T,Dm) including the D*x skip, final state (B,Dm,N) fp32).
    """
    t = x.shape[1]
    if impl == "auto":
        impl = "xla" if use_interpret() else "pallas"
    if impl == "pallas" and state0 is None:
        y, h = mamba_scan_pallas(x, delta, a, b, c)
    else:
        xp = pad_dim(x, 1, chunk)
        dp = pad_dim(delta, 1, chunk)      # delta=0 pad: exp(0*A)=1, inc=0
        bp = pad_dim(b, 1, chunk)
        cp = pad_dim(c, 1, chunk)
        # state after padded (identity) steps equals the state at t — exact
        y, h = _chunked_assoc(xp, dp, a, bp, cp, state0, chunk)
        y = y[:, :t].astype(x.dtype)
    y = y + (x.astype(jnp.float32) * d[None, None].astype(jnp.float32)
             ).astype(y.dtype)
    return y, h


@kernel_family("mamba_scan")
def build_kernel(config: EGPUConfig = EGPU_16T, *, use_pallas: bool = True,
                 chunk: int = 64) -> Kernel:
    """TinyCL kernel object: selective scan x/delta (B,T,Dm), a (Dm,N),
    b/c (B,T,N), d (Dm,) -> (y, final_state)."""
    impl = "auto" if use_pallas else "xla"
    exe = (lambda x, delta, a, b, c, d:
           mamba_scan(x, delta, a, b, c, d, chunk=chunk, impl=impl))
    return Kernel(
        name="mamba_scan",
        executor=exe,
        counts=lambda bsz, t, dm, n, itemsize=4: counts(bsz, t, dm, n,
                                                        itemsize),
        jitted=True,   # `mamba_scan` is already jax.jit-wrapped
    )
