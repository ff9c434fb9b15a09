"""GQA/MQA/MHA attention block with rotary embedding and a KV cache.

Three call modes share one parameter tree:

* :func:`attend_full`    — training / prefill over a whole sequence (flash
  attention kernel; causal or bidirectional for encoders);
* :func:`attend_decode`  — one new token against the cache (flash-decoding
  math in jnp: when the cache's T axis is sharded over ``model``, GSPMD turns
  the masked max/sum reductions into the partial-softmax all-reduce combine);
* cache init/update helpers used by the serving layer.

Projection weights keep *flattened* head dims — (d_model, H*hd) — so the TP
logical axes "heads"/"kv" are divisible by the 16-wide model axis for every
assigned arch (even minicpm's 36 heads: 36*64 = 2304 = 16*144).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import (active_axis_size, active_mesh,
                                    active_rules, constrain, spec_for)
from ..kernels.flash_attention.ops import _xla_full, flash_attention
from .config import ModelConfig
from .layers import apply_rotary, cdtype
from .params import ParamSpec, dense_spec

NEG_INF = -1e30

#: min sequence length for the context-parallel shard_map attention path
CP_MIN_SEQ = 8192


def _context_parallel_attention(q, k, v, cfg: ModelConfig) -> jax.Array:
    """Causal attention with q sequence-sharded over the ``model`` axis.

    For archs whose head count does not divide the 16-wide model axis
    (minicpm 36, paligemma 8), head-TP attention is impossible and naive
    GSPMD propagation all-gathers q/k/v INSIDE the flash pair-scan — 47.9 TB
    of link traffic on minicpm prefill_32k (EXPERIMENTS §Perf).  Instead:
    shard_map over "model" with q's S axis sharded; k/v are gathered ONCE
    per layer (they enter replicated); each shard runs chunked online-
    softmax attention over its q rows with a *traced* causal row offset
    (axis_index * S_local).

    Trade-off: no triangle skipping (a shard's chunk visibility depends on
    its dynamic offset) — 2x the minimal causal FLOPs, but distributed over
    16x more devices and with ~500x less traffic.  Zigzag CP would fix the
    imbalance; documented as future work in DESIGN.md.
    """
    from jax.sharding import PartitionSpec as P

    mesh = active_mesh()
    rules = active_rules()
    b, hq, s, d = q.shape
    batch_axes = spec_for(("batch",), rules, mesh, (b,))
    bspec = batch_axes[0] if len(batch_axes) else None
    q_spec = P(bspec, None, "model", None)
    kv_spec = P(bspec, None, None, None)
    scale = d ** -0.5

    def body(ql, kf, vf):
        offset = jax.lax.axis_index("model") * ql.shape[2]
        return _xla_full(ql, kf, vf, scale, True, bk=512, q_offset=offset)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                       out_specs=q_spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def attn_spec(cfg: ModelConfig, stacked: int = 0) -> Dict[str, ParamSpec]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    out = {
        "wq": dense_spec(d, h * hd, ("embed", "heads"), stacked=stacked),
        "wk": dense_spec(d, kvh * hd, ("embed", "kv"), stacked=stacked),
        "wv": dense_spec(d, kvh * hd, ("embed", "kv"), stacked=stacked),
        "wo": dense_spec(h * hd, d, ("heads", "embed"), stacked=stacked),
    }
    if cfg.qkv_bias:
        for name, width in (("bq", h * hd), ("bk", kvh * hd), ("bv", kvh * hd)):
            shape = (stacked, width) if stacked else (width,)
            axes = (("layers", "heads") if name == "bq" else ("layers", "kv")
                    ) if stacked else (("heads",) if name == "bq" else ("kv",))
            out[name] = ParamSpec(shape, axes, "zeros")
    return out


def _project_qkv(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, D) -> q (B, H, S, hd), k/v (B, KVH, S, hd), rotary applied."""
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cdtype(cfg)
    xq = jnp.dot(x.astype(dt), p["wq"].astype(dt))
    xk = jnp.dot(x.astype(dt), p["wk"].astype(dt))
    xv = jnp.dot(x.astype(dt), p["wv"].astype(dt))
    if cfg.qkv_bias:
        xq = xq + p["bq"].astype(dt)
        xk = xk + p["bk"].astype(dt)
        xv = xv + p["bv"].astype(dt)
    q = xq.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = xk.reshape(b, s, kvh, hd).transpose(0, 2, 1, 3)
    v = xv.reshape(b, s, kvh, hd).transpose(0, 2, 1, 3)
    if not cfg.is_encoder:   # encoders use additive positions at embed time
        q = apply_rotary(q, positions, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rotary(k, positions, cfg.rope_theta, cfg.rotary_pct)
    return q, k, v


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------
def attend_full(p, x: jax.Array, cfg: ModelConfig, *,
                positions: Optional[jax.Array] = None,
                return_kv: bool = False):
    """(B, S, D) -> (B, S, D); optionally also the (k, v) for cache build."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    causal = cfg.causal and not cfg.is_encoder
    model_tp = active_axis_size("model")
    if (causal and s >= CP_MIN_SEQ and model_tp > 1
            and cfg.n_heads % model_tp != 0):
        # context parallelism for non-head-divisible archs at long seq
        out = _context_parallel_attention(q, k, v, cfg)
    else:
        q = constrain(q, "batch", "heads", "seq", None)
        out = flash_attention(q, k, v, causal=causal)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    dt = cdtype(cfg)
    y = jnp.dot(out.astype(dt), p["wo"].astype(dt))
    if return_kv:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (batch, kvh, max_len, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (batch, kvh, max_len, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def cache_from_prefill(cfg: ModelConfig, k: jax.Array, v: jax.Array,
                       max_len: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Pad prefill (B, KVH, S, hd) K/V out to max_len cache arrays."""
    s = k.shape[2]
    pad = [(0, 0), (0, 0), (0, max_len - s), (0, 0)]
    return {"k": jnp.pad(k.astype(dtype), pad),
            "v": jnp.pad(v.astype(dtype), pad)}


# ---------------------------------------------------------------------------
# Decode (one token per sequence)
# ---------------------------------------------------------------------------
def write_rows(stack: jax.Array, rows: jax.Array, layer, pos: jax.Array,
               seq_axis: int = -2) -> jax.Array:
    """Write each sequence's one new cache row in place.

    ``stack`` (L, B, ...) is a whole layer stack whose T axis is
    ``seq_axis``; ``rows`` (B, ...) are the new rows, shaped like one
    layer's cache without T; ``pos`` (B,) is each sequence's position, so
    row ``b`` lands at ``[layer, b, ...]`` with T = ``pos[b]``.  With T
    second-minor the rows are contiguous: one scatter, every other axis
    indexed.  With T minor (a column) it is one ``dynamic_update_slice``
    per sequence, since a scatter there would make the compiler lay the
    whole stack out anew.  Either way a donated stack is updated in place:
    the rest of it is never read, rebuilt or copied.
    """
    rows = rows.astype(stack.dtype)
    axis = seq_axis % stack.ndim
    if axis == stack.ndim - 2:
        grid = rows.shape[:-1]                              # (B, ...)
        ix = [jnp.arange(n).reshape((n,) + (1,) * (len(grid) - 1 - i))
              for i, n in enumerate(grid)]
        at = pos.reshape((-1,) + (1,) * (len(grid) - 1))
        return stack.at[(layer, *ix, at)].set(rows)
    for b in range(rows.shape[0]):
        start = [layer, b] + [0] * (stack.ndim - 2)
        start[axis] = pos[b]
        stack = jax.lax.dynamic_update_slice(
            stack, jnp.expand_dims(rows[b], (0, 1, axis)), start)
    return stack


def attend_decode(p, x: jax.Array, cache: Dict[str, jax.Array], pos,
                  cfg: ModelConfig, layer=0):
    """x (B, 1, D) against layer ``layer`` of the stacked cache ``{"k",
    "v"}`` (L, B, KVH, T, hd); ``pos`` (B,) int32 is each sequence's
    absolute position.

    Writes each sequence's new key and value row at ``[layer, b, :,
    pos[b]]`` in place (:func:`write_rows`) and returns (y (B, 1, D), the
    updated stack).  The masked-softmax reduction over the cache's T axis
    is written so GSPMD's partial reductions implement flash-decoding when
    T is sharded (DESIGN.md §5).
    """
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None])

    k_stack = write_rows(cache["k"], k_new[:, :, 0], layer, pos)
    v_stack = write_rows(cache["v"], v_new[:, :, 0], layer, pos)
    dtype = k_stack.dtype
    k_cache = constrain(k_stack[layer], "batch", None, "kv_seq", None)
    v_cache = constrain(v_stack[layer], "batch", None, "kv_seq", None)

    group = h // kvh
    t = k_cache.shape[2]
    qd = q[:, :, 0].reshape(b, kvh, group, hd).astype(dtype)
    scale = hd ** -0.5
    # bf16 reads, f32 accumulation: never materialize an f32 cache copy
    # (an .astype(f32) on the cache doubles decode HBM — measured 5.6 GiB
    # on minicpm decode_32k before this, see EXPERIMENTS §Perf)
    s = jnp.einsum("bgqd,bgtd->bgqt", qd, k_cache,
                   preferred_element_type=jnp.float32) * scale  # (B,KVH,G,T)
    valid = (jnp.arange(t)[None] <= pos[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pexp = jnp.exp(s - m)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    o = jnp.einsum("bgqt,bgtd->bgqd", pexp.astype(dtype), v_cache,
                   preferred_element_type=jnp.float32) / l
    o = o.reshape(b, 1, h * hd)
    dt = cdtype(cfg)
    y = jnp.dot(o.astype(dt), p["wo"].astype(dt))
    return y, {"k": k_stack, "v": v_stack}
