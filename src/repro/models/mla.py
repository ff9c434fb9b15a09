"""Multi-head Latent Attention (DeepSeek-V2) with the compressed KV cache.

MLA projects hidden states into a low-rank latent ``c_kv`` (kv_lora_rank) plus
a shared rotary key slice; per-head K/V are up-projected from the latent.
The cache stores only ``c_kv`` (512) + ``k_rope`` (64) per token — 576 floats
instead of 2*128*128 = 32768 for an equivalent MHA — the paper-claimed 93 %
KV-cache reduction, and the reason deepseek-v2's decode_32k cell fits.

* train/prefill: latents are expanded to full per-head K/V and run through
  the shared flash-attention kernel (dk = 192 = 128 nope + 64 rope, dv = 128);
* decode: the **absorbed** form — W_UK folds into the query, W_UV into the
  output — so attention runs MQA-style against the 576-wide latent cache
  directly, never materializing per-head K/V.  This is the production
  DeepSeek serving trick and what makes the decode roofline memory-light.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..kernels.flash_attention.ops import flash_attention
from .attention import write_rows
from .config import ModelConfig
from .layers import (apply_rotary, cdtype, rms_norm_1d, yarn_frequencies,
                     yarn_mscale)
from .params import ParamSpec, dense_spec

NEG_INF = -1e30


def mla_spec(cfg: ModelConfig, stacked: int = 0) -> Dict[str, ParamSpec]:
    d, h = cfg.d_model, cfg.n_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    def vec(width, axes):
        shape = (stacked, width) if stacked else (width,)
        ax = (("layers",) + axes) if stacked else axes
        return ParamSpec(shape, ax, "ones")

    out = {
        # query path: d -> q_lora -> per-head (nope + rope)
        "wq_a": dense_spec(d, ql, ("embed", None), stacked=stacked),
        "q_norm": vec(ql, (None,)),
        "wq_b": dense_spec(ql, h * (nope + rope), (None, "heads"),
                           stacked=stacked),
        # kv path: d -> (kv_lora | shared rope key)
        "wkv_a": dense_spec(d, kvl + rope, ("embed", None), stacked=stacked),
        "kv_norm": vec(kvl, (None,)),
        "wk_b": dense_spec(kvl, h * nope, (None, "heads"), stacked=stacked),
        "wv_b": dense_spec(kvl, h * vd, (None, "heads"), stacked=stacked),
        "wo": dense_spec(h * vd, d, ("heads", "embed"), stacked=stacked),
    }
    return out


def _rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    """Rotary embedding of the rope channels; YaRN where configured."""
    if not cfg.yarn_factor:
        return apply_rotary(x, positions, cfg.rope_theta)
    return apply_rotary(
        x, positions, cfg.rope_theta,
        inv_freq=yarn_frequencies(x.shape[-1], cfg),
        mscale=(yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)))


def softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under YaRN."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _latents(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """x (B,S,D) -> (c_kv (B,S,kvl) normed, k_rope (B,1,S,rope) rotated)."""
    b, s, _ = x.shape
    kvl, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dt = cdtype(cfg)
    kv_a = jnp.dot(x.astype(dt), p["wkv_a"].astype(dt))
    c_kv = rms_norm_1d(kv_a[..., :kvl], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., kvl:].reshape(b, s, 1, rope).transpose(0, 2, 1, 3)
    k_rope = _rope(k_rope, positions, cfg)
    return c_kv, k_rope


def _queries(p, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """-> q_nope (B,H,S,nope), q_rope (B,H,S,rope)."""
    b, s, _ = x.shape
    h, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dt = cdtype(cfg)
    qa = rms_norm_1d(jnp.dot(x.astype(dt), p["wq_a"].astype(dt)),
                     p["q_norm"], cfg.norm_eps)
    qb = jnp.dot(qa.astype(dt), p["wq_b"].astype(dt))
    qb = qb.reshape(b, s, h, nope + rope).transpose(0, 2, 1, 3)
    q_nope, q_rope = qb[..., :nope], qb[..., nope:]
    q_rope = _rope(q_rope, positions, cfg)
    return q_nope, q_rope


# ---------------------------------------------------------------------------
# Train / prefill: expand latents, shared flash kernel
# ---------------------------------------------------------------------------
def mla_full(p, x: jax.Array, cfg: ModelConfig, *,
             positions: Optional[jax.Array] = None,
             return_cache: bool = False):
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(s)
    dt = cdtype(cfg)

    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latents(p, x, cfg, positions)

    k_nope = jnp.dot(c_kv.astype(dt), p["wk_b"].astype(dt))
    k_nope = k_nope.reshape(b, s, h, nope).transpose(0, 2, 1, 3)
    v = jnp.dot(c_kv.astype(dt), p["wv_b"].astype(dt))
    v = v.reshape(b, s, h, vd).transpose(0, 2, 1, 3)

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope, (b, h, s, rope))], axis=-1)
    q = constrain(q, "batch", "heads", "seq", None)
    out = flash_attention(q, k, v, causal=True, scale=softmax_scale(cfg))
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * vd)
    y = jnp.dot(out.astype(dt), p["wo"].astype(dt))
    if return_cache:
        return y, (c_kv, k_rope[:, 0])     # (B,S,kvl), (B,S,rope)
    return y


# ---------------------------------------------------------------------------
# Compressed cache: ``c_kv`` (B,T,kvl) and ``k_rope`` (B,rope,T).  The rope
# keys keep T minor: a 64-wide row is less than a TPU lane tile, and the
# compiler would lay such a leaf out with T minor anyway — storing it so
# lets the decode step write its column in place (``write_rows``).
# ---------------------------------------------------------------------------
def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cfg.qk_rope_head_dim, max_len), dtype),
    }


def mla_cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct(
            (batch, cfg.qk_rope_head_dim, max_len), dtype),
    }


def mla_cache_from_prefill(cfg: ModelConfig, c_kv, k_rope, max_len: int,
                           dtype=jnp.bfloat16):
    """Prefill's (B,S,kvl) latents and (B,S,rope) keys -> the cache,
    padded out to ``max_len``."""
    s = c_kv.shape[1]
    pad = [(0, 0), (0, max_len - s), (0, 0)]
    return {"c_kv": jnp.pad(c_kv.astype(dtype), pad),
            "k_rope": jnp.pad(k_rope.astype(dtype).swapaxes(1, 2),
                              [(0, 0), (0, 0), (0, max_len - s)])}


# ---------------------------------------------------------------------------
# Decode: absorbed MQA-style attention against the latent cache
# ---------------------------------------------------------------------------
def mla_decode(p, x: jax.Array, cache: Dict[str, jax.Array], pos,
               cfg: ModelConfig, layer=0):
    """x (B,1,D) against layer ``layer`` of the stacked latent cache
    ``{"c_kv" (L,B,T,kvl), "k_rope" (L,B,rope,T)}``; ``pos`` (B,) int32 is
    each sequence's absolute position.  Writes each sequence's new latent
    and rope key at T = ``pos[b]`` of ``[layer, b]`` in place and returns
    (y (B,1,D), the updated stack).  Attention runs in latent space:

    score_h(t) = q_nope_h · W_UK_h c_kv[t]  +  q_rope_h · k_rope[t]
               = (W_UK_hᵀ q_nope_h) · c_kv[t] + q_rope_h · k_rope[t]

    so each head's query is *absorbed* to (kvl + rope) and the cache is the
    only per-token state read — one MQA pass over 576-wide latents.
    """
    b = x.shape[0]
    h = cfg.n_heads
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    dt = cdtype(cfg)
    positions = pos[:, None]

    q_nope, q_rope = _queries(p, x, cfg, positions)      # (B,H,1,·)
    c_new, k_rope_new = _latents(p, x, cfg, positions)   # (B,1,kvl),(B,1,1,rope)

    c_stack = write_rows(cache["c_kv"], c_new[:, 0], layer, pos)
    r_stack = write_rows(cache["k_rope"], k_rope_new[:, 0, 0], layer, pos,
                         seq_axis=-1)
    dtype = c_stack.dtype
    c_kv = constrain(c_stack[layer], "batch", "kv_seq", None)
    k_rope = constrain(r_stack[layer], "batch", None, "kv_seq")

    # absorb W_UK into the query:  q_lat (B,H,kvl)
    wk_b = p["wk_b"].astype(jnp.float32).reshape(kvl, h, nope)
    q_lat = jnp.einsum("bhd,khd->bhk",
                       q_nope[:, :, 0].astype(jnp.float32), wk_b)
    # scores over the latent cache + shared rope key — bf16 cache reads
    # with f32 accumulation (no f32 cache copy; see attention.py note)
    t = c_kv.shape[1]
    scale = softmax_scale(cfg)
    s_lat = jnp.einsum("bhk,btk->bht", q_lat.astype(dtype), c_kv,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bhr,brt->bht", q_rope[:, :, 0].astype(dtype),
                        k_rope, preferred_element_type=jnp.float32)
    s = (s_lat + s_rope) * scale
    valid = (jnp.arange(t)[None] <= pos[:, None])[:, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pexp = jnp.exp(s - m)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    o_lat = jnp.einsum("bht,btk->bhk", pexp.astype(dtype), c_kv,
                       preferred_element_type=jnp.float32) / l

    # absorb W_UV into the output:  (B,H,kvl) x (kvl,H,vd) -> (B,H,vd)
    wv_b = p["wv_b"].astype(jnp.float32).reshape(kvl, h, vd)
    o = jnp.einsum("bhk,khd->bhd", o_lat, wv_b)
    o = o.reshape(b, 1, h * vd)
    y = jnp.dot(o.astype(dt), p["wo"].astype(dt))
    return y, {"c_kv": c_stack, "k_rope": r_stack}
