"""Stockham FFT Pallas kernel — the whole transform VMEM-resident.

TPU adaptation of the paper's §VIII-C kernel: the e-GPU ping-pongs between
two D$-resident buffers with a barrier per stage; on TPU the natural
equivalent is to keep both planes in VMEM for the entire transform and unroll
the log2(n) stages inside a single pallas_call — the "barrier" becomes the
SSA dependency between stages, and the ping-pong becomes value renaming.
This removes every HBM round-trip between stages (the optimization the paper
gets from cache residency, §IV-B).

The grid runs over blocks of eight signals (a whole sublane tile); each grid
step transforms its signals one after another, each of length ``n``
(n * 16 B of VMEM for re/im + twiddles — up to n = 64k fits comfortably).
Twiddles are computed in-kernel from iota, so the kernel has no side tables
to DMA.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import pad_dim, use_interpret


ROWS = 8   # signals per block: the sublane tile of a float32 vreg


def _fft_kernel(re_ref, im_ref, ore_ref, oim_ref, *, n: int):
    stages = n.bit_length() - 1
    for row in range(re_ref.shape[0]):
        re = re_ref[pl.ds(row, 1), :].reshape(n, 1)
        im = im_ref[pl.ds(row, 1), :].reshape(n, 1)
        for _ in range(stages):
            l = re.shape[1]
            r = re.shape[0] // 2
            # twiddles from 2-D iota (TPU requires >= 2-D, integer iota):
            # angle = -pi * j / l
            j = jax.lax.broadcasted_iota(jnp.int32, (1, l), 1).astype(
                jnp.float32)
            ang = (-math.pi / l) * j
            wr, wi = jnp.cos(ang), jnp.sin(ang)
            ar, ai = re[:r], im[:r]
            br, bi = re[r:], im[r:]
            tr = wr * br - wi * bi
            ti = wr * bi + wi * br
            re = jnp.concatenate([ar + tr, ar - tr], axis=1)
            im = jnp.concatenate([ai + ti, ai - ti], axis=1)
        ore_ref[pl.ds(row, 1), :] = re.reshape(1, n)
        oim_ref[pl.ds(row, 1), :] = im.reshape(1, n)


@jax.jit
def fft_pallas(re: jax.Array, im: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched FFT: re/im shaped (batch, n), n a power of two.  A batch of
    more than :data:`ROWS` signals is zero-padded to a multiple of it."""
    b, n = re.shape
    assert 1 << (n.bit_length() - 1) == n, f"n={n} must be a power of two"
    rows = min(b, ROWS)
    re = pad_dim(re.astype(jnp.float32), 0, rows)
    im = pad_dim(im.astype(jnp.float32), 0, rows)
    bp = re.shape[0]
    spec = pl.BlockSpec((rows, n), lambda i: (i, 0))
    ore, oim = pl.pallas_call(
        functools.partial(_fft_kernel, n=n),
        grid=(bp // rows,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((bp, n), jnp.float32),
                   jax.ShapeDtypeStruct((bp, n), jnp.float32)],
        interpret=use_interpret(),
        name="fft_pallas",
    )(re, im)
    return ore[:b], oim[:b]
