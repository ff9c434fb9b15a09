"""FIR filter Pallas kernels: a banded Toeplitz product on the MXU for
floats, a shifted multiply-accumulate on the VPU for Q15 integers.

Float path (``fxp_shift=None``).  A signal is viewed as rows ``X`` of
shape ``(n/128, 128)``: lane-dense, with all 8 sublanes of a vreg in use.
Output row ``r`` of the causal filter ``y[m] = sum_t h[t] x[m - t]`` is

    Y[r] = sum_{k=0}^{K-1} X[r - k] @ T_k,     K = ceil((taps - 1) / 128) + 1,

with ``X[r - k] = 0`` before the signal starts and the band matrices
``T_k[i, j] = h[128 k + j - i]`` where ``0 <= 128 k + j - i < taps``, else
0 (:func:`band_matrices`; for 128 taps, ``T_0`` is upper triangular and
``T_1`` strictly lower).  The wrapper builds the ``T_k`` from ``h`` once
per call.  A grid step takes a tile of rows, the whole recording at the
served size (512 rows, 256 KiB), plus ``halo`` rows that end just before
the tile: a second, ``(halo, 128)`` view of the same input, zeroed on the
first tile.  Each band is one dot of the haloed tile; row ``r`` of band
``k`` is row ``r - k`` of that product, a sublane roll by ``k``.  Operands
are float32, every dot runs at ``Precision.HIGHEST`` and accumulates in
float32 — the precision the filter is specified in.

Integer path (``fxp_shift`` set).  The e-GPU has no FPU (§IV-A):
the paper filters in Q15 fixed point, int32 MACs and a renormalising shift,
and the MXU takes no int32, so this path stays on the VPU and bit-exact
with ``fir_ref``.  Each grid step produces one ``(1, block)`` block of
outputs and receives the previous block as a second view of the input
(index map ``max(i - 1, 0)``) for the ``taps - 1`` samples of history; the
taps loop is unrolled, each iteration a shifted static slice — the VPU
analogue of the e-GPU's register sliding window (§VIII-C).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv, round_up, use_interpret

LANES = 128


def bands(taps: int) -> int:
    """``K``: band matrices a ``taps``-tap filter spans over 128-lane rows."""
    return cdiv(taps - 1, LANES) + 1


def halo_rows(taps: int) -> int:
    """Rows of history a tile needs (``K - 1``), rounded up to a sublane
    tile so the halo is a block of its own."""
    return round_up(max(bands(taps) - 1, 1), 8)


def band_matrices(h: jax.Array) -> jax.Array:
    """``(K, 128, 128)`` float32: ``T[k, i, j] = h[128 k + j - i]`` inside
    the filter, else 0."""
    taps = h.shape[0]
    i = jnp.arange(LANES)[:, None]
    j = jnp.arange(LANES)[None, :]
    t = LANES * jnp.arange(bands(taps))[:, None, None] + j - i
    inside = (t >= 0) & (t < taps)
    return jnp.where(inside, h.astype(jnp.float32)[jnp.clip(t, 0, taps - 1)],
                     0.0)


def _fir_band_kernel(halo_ref, x_ref, t_ref, o_ref, *, halo: int):
    i = pl.program_id(0)
    prev = jnp.where(i == 0, 0.0, halo_ref[...])
    x = x_ref[...]
    ext = jnp.concatenate([prev, x], axis=0)     # (halo + rows, 128)

    def band(rows, k):
        return jnp.dot(rows, t_ref[k], precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    acc = band(x, 0)
    for k in range(1, t_ref.shape[0]):
        # row r of the tile takes row r - k of the haloed product
        acc = acc + pltpu.roll(band(ext, k), k, 0)[halo:]
    o_ref[...] = acc


def _band_call(x: jax.Array, h: jax.Array, rows: int):
    n, taps = x.shape[0], h.shape[0]
    halo = halo_rows(taps)
    assert n % (LANES * rows) == 0 and rows % halo == 0, (n, rows, taps)
    x2 = x.astype(jnp.float32).reshape(n // LANES, LANES)
    t = band_matrices(h)
    step = rows // halo
    return dict(
        kernel=functools.partial(_fir_band_kernel, halo=halo),
        grid=(n // (LANES * rows),),
        in_specs=[
            pl.BlockSpec((halo, LANES),
                         lambda i: (jnp.maximum(i * step - 1, 0), 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec(t.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
    ), (x2, x2, t)


def _fir_fixed_kernel(x_prev_ref, x_cur_ref, h_ref, o_ref, *, taps: int,
                      block: int, shift: int):
    i = pl.program_id(0)
    # (1, block) layout: TPU wants >=2-D; lane dim = block
    prev = x_prev_ref[...]
    cur = x_cur_ref[...]
    # zero history for the first block (index map clamps i-1 to 0)
    prev = jnp.where(i == 0, jnp.zeros_like(prev), prev)
    w = jnp.concatenate([prev, cur], axis=-1)      # (1, 2*block)
    acc = jnp.zeros(cur.shape, jnp.int32)
    for t in range(taps):
        # y[j] += h[t] * x[j - t]  ->  w[block + j - t]
        sl = jax.lax.slice_in_dim(w, block - t, 2 * block - t, axis=1)
        acc = acc + h_ref[0, t] * sl.astype(jnp.int32)
    o_ref[...] = jnp.right_shift(acc, shift).astype(o_ref.dtype)


def _fixed_call(x: jax.Array, h: jax.Array, block: int, shift: int):
    n, taps = x.shape[0], h.shape[0]
    assert n % block == 0 and block >= taps, (n, block, taps)
    x2 = x.reshape(1, n)
    return dict(
        kernel=functools.partial(_fir_fixed_kernel, taps=taps, block=block,
                                 shift=shift),
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (0, jnp.maximum(i - 1, 0))),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, taps), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
    ), (x2, x2, h.reshape(1, taps))


@functools.partial(jax.jit, static_argnames=("block", "fxp_shift"))
def fir_pallas(x: jax.Array, h: jax.Array, *, block: int,
               fxp_shift: int | None = None) -> jax.Array:
    """Causal FIR via Pallas (ops.fir pads and validates).

    Float (``fxp_shift=None``): the banded Toeplitz kernel, ``block`` rows
    of 128 samples a grid step; ``x`` length a multiple of ``128 * block``
    and ``block`` of ``halo_rows(taps)``.  Integer: the Q15 kernel with
    that shift, ``block`` samples a grid step; ``x`` length a multiple of
    ``block`` and ``block >= taps``."""
    if fxp_shift is None:
        call, operands = _band_call(x, h, block)
    else:
        call, operands = _fixed_call(x, h, block, fxp_shift)
    out = pl.pallas_call(**call, interpret=use_interpret(),
                         name="fir_pallas")(*operands)
    return out.reshape(x.shape[0])
