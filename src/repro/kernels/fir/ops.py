"""jit'd public wrapper for the FIR kernel + TinyCL registration."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.device import EGPU_16T, EGPUConfig
from ...core.program import kernel_family
from ...core.runtime import Kernel
from ..common import cdiv, pad_dim, round_up
from .fir import LANES, fir_pallas, halo_rows
from .ref import FXP_SHIFT, counts as fir_counts, fir_ref


@functools.partial(jax.jit, static_argnames=("block",))
def fir(x: jax.Array, h: jax.Array, block: int = 512) -> jax.Array:
    """Causal FIR filter of any length via the Pallas kernels.

    Integer inputs take the Q15 kernel, ``block`` samples a grid step;
    float inputs the banded Toeplitz kernel, up to ``block`` rows of 128
    samples a grid step."""
    n = x.shape[0]
    taps = h.shape[0]
    if jnp.issubdtype(x.dtype, jnp.integer):
        block = max(block, round_up(taps, 128))
        y = fir_pallas(pad_dim(x, 0, block), h, block=block,
                       fxp_shift=FXP_SHIFT)
        return y[:n]
    halo = halo_rows(taps)
    rows = min(round_up(block, halo), round_up(cdiv(n, LANES), halo))
    y = fir_pallas(pad_dim(x, 0, LANES * rows), h, block=rows)
    return y[:n]


@kernel_family("fir")
def build_kernel(config: EGPUConfig = EGPU_16T, *,
                 use_pallas: bool = True) -> Kernel:
    knobs = config.tpu_knobs()
    block = max(512, knobs.lane_tile)
    exe = (lambda x, h: fir(x, h, block)) if use_pallas else fir_ref
    return Kernel(
        name="fir",
        executor=exe,
        counts=lambda n, taps, itemsize=4: fir_counts(n, taps, itemsize),
        jitted=use_pallas,   # `fir` is already jax.jit-wrapped
    )
