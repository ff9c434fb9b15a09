"""SVM decision-function Pallas kernel (RBF / linear).

Grid over support-vector blocks (the reduction axis): each step computes a
(q_block x m_block) kernel-matrix tile from a dots GEMM on the MXU plus VPU
exp, then accumulates ``K_tile @ alpha_tile`` into a VMEM scratch — so the
full kernel matrix never materializes in HBM, mirroring the D$-resident
discipline of the paper's kernels (§IV-B).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import use_interpret


def _svm_kernel(x_ref, xsq_ref, sv_ref, svsq_ref, a_ref, o_ref, acc_ref, *,
                steps: int, gamma: float | None):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                       # (bq, d)
    sv = sv_ref[...]                     # (bm, d)
    dots = jnp.dot(x, sv.T, preferred_element_type=jnp.float32)
    if gamma is None:
        k = dots
    else:
        d2 = xsq_ref[...] + svsq_ref[...] - 2.0 * dots   # (bq,1)+(1,bm)
        k = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
    # masked alpha (padding rows carry alpha = 0) folds the tail for free
    acc_ref[...] += jnp.dot(k, a_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == steps - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bq", "bm", "gamma"))
def svm_pallas(x: jax.Array, sv: jax.Array, alpha: jax.Array,
               *, bq: int = 8, bm: int = 128,
               gamma: float | None = None) -> jax.Array:
    """Sum_i alpha_i K(sv_i, x) for padded shapes: x (q, d), sv (m, d),
    alpha (m, 1); q % bq == 0, m % bm == 0."""
    q, d = x.shape
    m, _ = sv.shape
    assert q % bq == 0 and m % bm == 0, (x.shape, sv.shape, bq, bm)
    steps = m // bm
    xsq = jnp.sum(x * x, axis=1, keepdims=True)          # (q, 1)
    svsq = jnp.sum(sv * sv, axis=1)[None, :]             # (1, m)
    out = pl.pallas_call(
        functools.partial(_svm_kernel, steps=steps, gamma=gamma),
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((bq, d), lambda s: (0, 0)),
            pl.BlockSpec((bq, 1), lambda s: (0, 0)),
            pl.BlockSpec((bm, d), lambda s: (s, 0)),
            pl.BlockSpec((1, bm), lambda s: (0, s)),
            pl.BlockSpec((bm, 1), lambda s: (s, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1), lambda s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=use_interpret(),
        name="svm_pallas",
    )(x.astype(jnp.float32), xsq, sv.astype(jnp.float32), svsq,
      alpha.reshape(m, 1).astype(jnp.float32))
    return out[:, 0]
