"""MXU-tiled GeMM Pallas kernel, knob-driven (e-GPU Table-II discipline).

Grid ``(M/bm, N/bn, K/bk)`` with a VMEM accumulator scratch: the K dimension
is the innermost (sequential on TPU) grid axis, so each (i, j) output tile
accumulates across K steps while Pallas double-buffers the A/B tiles —
exactly the warp-style latency hiding the paper gets from 4 concurrent warps
over a 4-cycle D$ (§VII-A), transplanted to HBM->VMEM DMAs.

Tile shapes come from :class:`repro.core.KernelKnobs` (the TPU projection of
the e-GPU's threads / warps / D$ knobs) and are validated against the VMEM
budget with :func:`repro.core.check_vmem_budget`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.device import KernelKnobs, check_vmem_budget
from ..common import use_interpret


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if jnp.issubdtype(acc_ref.dtype, jnp.integer):
        # The v5e MXU takes no int32 operands, so integer GeMM is an exact
        # VPU multiply-accumulate: one rank-1 update per k of the tile
        # (wrapping int32 arithmetic, as numpy's int32 matmul).
        acc = acc_ref[...]
        for kk in range(a_ref.shape[1]):
            col = a_ref[:, pl.ds(kk, 1)].astype(acc.dtype)
            row = b_ref[pl.ds(kk, 1), :].astype(acc.dtype)
            acc = acc + col * row
        acc_ref[...] = acc
    else:
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype"))
def gemm_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 128, out_dtype=None) -> jax.Array:
    """C = A @ B.  Shapes must already be padded to multiples of the tiles
    (``ops.gemm`` handles padding/cropping)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)
    acc_dtype = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    out_dtype = out_dtype or acc_dtype
    k_steps = k // bk

    grid = (m // bm, n // bn, k_steps)
    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=use_interpret(),
        name="gemm_pallas",
    )(a, b)


def tiles_from_knobs(knobs: KernelKnobs, m: int, n: int, k: int,
                     itemsize: int = 4) -> tuple[int, int, int]:
    """Pick (bm, bn, bk) from the e-GPU knob projection, MXU-aligned, within
    the VMEM budget (the D$-size knob)."""
    bn = min(knobs.lane_tile, max(128, n))
    bm = min(max(knobs.sublane_tile * 16, 128), max(128, m))
    bk = 128
    # shrink bm until the A, B, output and accumulator blocks (x pipeline
    # depth) fit the budget
    while True:
        blocks = (bm * bk * itemsize, bk * bn * itemsize, bm * bn * 4,
                  bm * bn * 4)
        try:
            check_vmem_budget(knobs, *blocks)
            break
        except ValueError:
            if bm > 128:
                bm //= 2
            elif bn > 128:
                bn //= 2
            else:
                break
    return bm, bn, bk
