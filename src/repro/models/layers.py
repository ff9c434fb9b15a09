"""Shared neural building blocks: norms, MLPs, rotary embedding, embeddings.

Convention: params are fp32 pytrees (see params.py); activations are cast to
the config compute dtype (bf16 in production) at the matmul boundary, with
norms and softmax in fp32.  Functions take (params_subtree, x, cfg) and are
pure.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .params import ParamSpec, dense_spec


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def norm_spec(cfg: ModelConfig, stacked: int = 0) -> Dict[str, ParamSpec]:
    shape = (stacked, cfg.d_model) if stacked else (cfg.d_model,)
    axes = ("layers", "embed") if stacked else ("embed",)
    out = {"scale": ParamSpec(shape, axes, "ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamSpec(shape, axes, "zeros")
    return out


def apply_norm(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_1d(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------
def matmul(x: jax.Array, w: jax.Array, cfg: ModelConfig) -> jax.Array:
    return jnp.dot(x.astype(cdtype(cfg)), w.astype(cdtype(cfg)))


def act_fn(cfg: ModelConfig):
    return jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu


def mlp_spec(cfg: ModelConfig, d_ff: int, stacked: int = 0):
    d = cfg.d_model
    out = {
        "wi": dense_spec(d, d_ff, ("embed", "mlp"), stacked=stacked),
        "wo": dense_spec(d_ff, d, ("mlp", "embed"), stacked=stacked),
    }
    if cfg.gated_mlp:
        out["wg"] = dense_spec(d, d_ff, ("embed", "mlp"), stacked=stacked)
    return out


def apply_mlp(p, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Gated MLP wo( act(x wg) * (x wi) ) — llama/gemma family — or the
    classic wo( act(x wi) ) two-matmul form (hubert/BERT lineage)."""
    if cfg.gated_mlp:
        g = act_fn(cfg)(matmul(x, p["wg"], cfg))
        h = g * matmul(x, p["wi"], cfg)
    else:
        h = act_fn(cfg)(matmul(x, p["wi"], cfg))
    return matmul(h, p["wo"], cfg)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, cfg: ModelConfig) -> jax.Array:
    """DeepSeek-V2's YaRN inverse frequencies (``DeepseekV2YarnRotary
    Embedding``): ``base^(-2i/d)`` kept for the fast-rotating channels,
    divided by ``factor`` for the slow ones, blended by a linear ramp over
    the correction range that ``beta_fast`` and ``beta_slow`` set."""
    def corr_dim(rotations):
        return (dim * math.log(cfg.yarn_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(corr_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(dim, cfg.rope_theta)
    inter = 1.0 / (cfg.yarn_factor * cfg.rope_theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def apply_rotary(x: jax.Array, positions: jax.Array, theta: float,
                 rotary_pct: float = 1.0, *,
                 inv_freq: jax.Array | None = None,
                 mscale: float = 1.0) -> jax.Array:
    """x (..., S, D); positions (S,) or (B, S).  Rotates the first
    ``rotary_pct * D`` channels (pairwise halves convention).
    ``inv_freq`` replaces the plain ``theta`` frequencies (YaRN) and
    ``mscale`` scales cos and sin."""
    d = x.shape[-1]
    rd = int(d * rotary_pct)
    rd -= rd % 2
    if rd == 0:
        return x
    xr, xp = x[..., :rd], x[..., rd:]
    freqs = (rope_frequencies(rd, theta) if inv_freq is None
             else inv_freq)                                   # (rd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs    # (..., S, rd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    while cos.ndim < xr.ndim:                                 # add head axis
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), xp], axis=-1)


def sinusoidal_positions(seq: int, d: int, offset: int = 0) -> jax.Array:
    """Classic transformer sin/cos table (audio-encoder positional stub)."""
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div[: (d + 1) // 2]))
    return pe


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------
def embed_spec(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    # 1/sqrt(d) embeddings keep tied-head logits O(1) at init (gemma-style
    # scale_emb = sqrt(d) archs re-scale the lookup back up).
    out = {"embedding": ParamSpec((cfg.vocab_padded, cfg.d_model),
                                  ("vocab", "embed"), "normal",
                                  cfg.d_model ** -0.5)}
    if not cfg.tie_embeddings:
        out["lm_head"] = dense_spec(cfg.d_model, cfg.vocab_padded,
                                    ("embed", "vocab"))
    return out


def embed_tokens(p, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = p["embedding"].astype(cdtype(cfg))[tokens]
    if cfg.scale_emb != 1.0:
        x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    return x


def logits_from_hidden(p, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = p.get("lm_head")
    if w is None:
        w = p["embedding"].T
    logits = jnp.dot(h.astype(cdtype(cfg)), w.astype(cdtype(cfg)))
    logits = logits.astype(jnp.float32)
    if cfg.logit_scale_base:
        logits = logits / (cfg.d_model / cfg.logit_scale_base)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy; logits fp32 (B, S, Vp), labels (B, S)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def residual_scale(cfg: ModelConfig) -> float:
    """MiniCPM depth-scaled residuals: each block output is multiplied by
    scale_depth / sqrt(n_layers)."""
    if cfg.scale_depth:
        return cfg.scale_depth / math.sqrt(cfg.n_layers)
    return 1.0
