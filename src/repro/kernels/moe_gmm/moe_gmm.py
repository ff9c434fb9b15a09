"""Grouped matmul over expert-sorted rows: a Pallas TPU kernel.

``x`` (M, K) holds the rows routed to this chip's experts, sorted by
expert; every expert's run starts at a multiple of the row tile ``tm`` and
is padded to one, so each ``tm``-row tile belongs to exactly one expert.
Three scalar-prefetch operands steer the grid: ``tile_expert`` (M / tm,),
the expert whose weight block each tile multiplies, ``n_active`` (1,),
how many leading tiles hold routed rows, and ``layer`` (1,), which layer
of the stacked weights (L, E, K, N) to read.

Grid (M / tm, N / tn, K / tk), k innermost, accumulating in an f32 VMEM
scratch.  A tile past ``n_active`` is skipped: its body does nothing and
its index maps repeat the blocks of the last active step, so the pipeline
fetches no operand and writes back no output for it.  So an expert that
no row was routed to has no weight block read (the one exception: with no
active tile at all the grid's first step still fetches one block).  Rows
of skipped tiles are left unwritten; callers read routed rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


def _gmm_kernel(tile_expert_ref, n_active_ref, layer_ref, x_ref, w_ref,
                o_ref, acc_ref, *, nk: int):
    del tile_expert_ref, layer_ref
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(i < n_active_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "tn"))
def moe_gmm_pallas(x: jax.Array, w: jax.Array, tile_expert: jax.Array,
                   n_active: jax.Array, layer: jax.Array, *, tm: int,
                   tk: int, tn: int) -> jax.Array:
    """x (M, K), w (L, E, K, N) stacked over layers, tile_expert (M/tm,)
    int32, n_active (1,) int32, layer (1,) int32 -> (M, N) in
    ``x.dtype``, with layer ``layer``'s experts; M, K, N divisible by tm,
    tk, tn.  Taking the whole stack keeps XLA from copying one layer's
    experts out of it for the call.  ``tile_expert`` of a skipped tile
    must equal that of the last active one (``ops.tile_plan`` makes it
    so)."""
    m, kdim = x.shape
    n = w.shape[3]
    assert m % tm == 0 and kdim % tk == 0 and n % tn == 0, (
        x.shape, w.shape, tm, tk, tn)
    nk, nn = kdim // tk, n // tn

    def _steer(i, j, k, na):
        act = i < na[0]
        last = jnp.maximum(na[0] - 1, 0)
        return (jnp.where(act, i, last), jnp.where(act, j, nn - 1),
                jnp.where(act, k, nk - 1))

    def x_map(i, j, k, te, na, ly):
        ii, _, kk = _steer(i, j, k, na)
        return ii, kk

    def w_map(i, j, k, te, na, ly):
        _, jj, kk = _steer(i, j, k, na)
        return ly[0], te[i], kk, jj

    def o_map(i, j, k, te, na, ly):
        ii, jj, _ = _steer(i, j, k, na)
        return ii, jj

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(m // tm, nn, nk),
        in_specs=[pl.BlockSpec((tm, tk), x_map),
                  pl.BlockSpec((None, None, tk, tn), w_map)],
        out_specs=pl.BlockSpec((tm, tn), o_map),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=use_interpret(),
        name="moe_gmm_pallas",
    )(tile_expert, n_active, layer, x, w)
