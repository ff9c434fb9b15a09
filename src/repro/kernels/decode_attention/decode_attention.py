"""Flash-decoding Pallas TPU kernel.

One new token per sequence against a long KV cache.  The grid is
(B, KVH, T/bk) with the cache axis innermost: each step streams one KV block
through VMEM and updates the online-softmax state for the *group* of q heads
sharing that kv head (GQA), so the MXU sees a (group x bk) logits tile
instead of a vector — bandwidth-bound by the KV read, exactly at the memory
roofline.

The kernel optionally emits the partial (acc, m, l) instead of the
normalized output; the model layer psum-combines partials across
sequence-sharded cache shards (flash-decoding across the `model` mesh axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, steps: int,
                   partial: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # (g, dk)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, dk)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    m_prev = m_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                # (g, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)                   # (bk, dv)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == steps - 1)
    def _store():
        l = l_ref[...]
        if partial:
            o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
            m_out_ref[0, 0] = m_ref[...]
            l_out_ref[0, 0] = l
        else:
            o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                           ).astype(o_ref.dtype)
            m_out_ref[0, 0] = m_ref[...]
            l_out_ref[0, 0] = l


@functools.partial(jax.jit, static_argnames=("scale", "bk", "partial"))
def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            scale: float | None = None, bk: int = 512,
                            partial: bool = False):
    """q (B, H, Dk) x k (B, KVH, T, Dk) x v (B, KVH, T, Dv).

    Returns (out (B,H,Dv), m (B,H,1), l (B,H,1)); ``out`` is normalized
    unless ``partial``.  T % bk == 0 (ops pads with masked keys is NOT done
    here — decode caches are always block-aligned by the serving layer).
    """
    b, h, dk = q.shape
    kvh, t = k.shape[1], k.shape[2]
    dv = v.shape[3]
    g = h // kvh
    assert t % bk == 0, (t, bk)
    scale = (dk ** -0.5) if scale is None else scale
    steps = t // bk
    grid = (b, kvh, steps)
    qg = q.reshape(b, kvh, g, dk)
    out, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, steps=steps,
                          partial=partial),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, dk), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b_, h_, j: (b_, h_, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dv), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, dv),
                                 jnp.float32 if partial else q.dtype),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, dv), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=use_interpret(),
        name="decode_attention_pallas",
    )(qg, k, v)
    return (out.reshape(b, h, dv), m.reshape(b, h, 1), l.reshape(b, h, 1))
