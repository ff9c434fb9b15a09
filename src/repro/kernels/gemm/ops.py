"""jit'd public wrapper for the GeMM kernel: padding, knob plumbing, TinyCL
kernel registration."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.device import EGPU_16T, EGPUConfig, KernelKnobs
from ...core.program import kernel_family
from ...core.runtime import Kernel
from ..common import pad_dim, round_up
from .gemm import gemm_pallas, tiles_from_knobs
from .ref import counts as gemm_counts
from .ref import gemm_ref


@functools.partial(jax.jit, static_argnames=("knobs",))
def gemm(a: jax.Array, b: jax.Array, knobs: KernelKnobs | None = None) -> jax.Array:
    """C = A @ B via the Pallas kernel, any (m, k) x (k, n) shapes/dtypes."""
    knobs = knobs or EGPU_16T.tpu_knobs()
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = tiles_from_knobs(knobs, m, n, k, a.dtype.itemsize)
    if jnp.issubdtype(a.dtype, jnp.integer):
        # the integer kernel unrolls one VPU update of the whole output tile
        # per k: 128x128 tiles keep that code small
        bm, bn = min(bm, 128), min(bn, 128)
    bm, bn, bk = min(bm, round_up(m, 8)), min(bn, round_up(n, 128)), min(bk, round_up(k, 128))
    ap = pad_dim(pad_dim(a, 0, bm), 1, bk)
    bp = pad_dim(pad_dim(b, 0, bk), 1, bn)
    out = gemm_pallas(ap, bp, bm=bm, bn=bn, bk=bk)
    return out[:m, :n]


@kernel_family("gemm")
def build_kernel(config: EGPUConfig = EGPU_16T, *,
                 use_pallas: bool = True) -> Kernel:
    """TinyCL kernel object for queue dispatch (registry builder)."""
    knobs = config.tpu_knobs()
    exe = (lambda a, b: gemm(a, b, knobs)) if use_pallas else gemm_ref
    return Kernel(
        name="gemm",
        executor=exe,
        counts=lambda m, n, k, itemsize=4: gemm_counts(m, n, k, itemsize),
        jitted=use_pallas,   # `gemm` is already jax.jit-wrapped
    )
