"""FlashAttention-2 Pallas TPU kernel (GQA-aware, mixed Dk/Dv).

Grid (B, H, S/bq, T/bk) with the kv axis innermost (sequential on TPU), so
each (b, h, i) output tile streams kv blocks through VMEM while the online
softmax state (m, l, acc) lives in VMEM scratch — the e-GPU paper's
cache-residency discipline (§IV-B) applied to the attention working set.
GQA is expressed in the k/v index maps (kv head = q head // group), so no
repeated kv ever materializes.

Causal masking is block-sparse: fully-masked kv blocks are skipped with
``pl.when`` (no MXU work, the DMA is still scheduled by the grid — Mosaic
elides stores), halving effective FLOPs at S == T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, kv_steps: int,
                  q_offset: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # first absolute q row of this tile vs first kv col: skip if block fully
    # above the diagonal
    q_lo = q_offset + i * bq
    run = (not causal) or (q_lo + bq - 1 >= j * bk)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, dk)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, dk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qi = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= kj, s, NEG_INF)
        m_prev = m_ref[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)             # (bk, dv)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kv_steps - 1)
    def _store():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk",
                                             "q_offset"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, scale: float | None = None,
                           bq: int = 128, bk: int = 128,
                           q_offset: int = 0) -> jax.Array:
    """q (B,H,S,Dk), k (B,KVH,T,Dk), v (B,KVH,T,Dv) -> (B,H,S,Dv).
    S % bq == 0 and T % bk == 0 (ops.flash_attention pads)."""
    b, h, s, dk = q.shape
    kvh, t = k.shape[1], k.shape[2]
    dv = v.shape[3]
    group = h // kvh
    assert s % bq == 0 and t % bk == 0, (q.shape, k.shape, bq, bk)
    scale = (dk ** -0.5) if scale is None else scale
    kv_steps = t // bk
    grid = (b, h, s // bq, kv_steps)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        kv_steps=kv_steps, q_offset=q_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dk), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, dk),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=use_interpret(),
        name="flash_attention_pallas",
    )(q, k, v)
