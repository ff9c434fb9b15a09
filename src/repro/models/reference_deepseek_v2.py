"""Plain float32 forward of DeepSeek-V2, for tests to compare the served
path against.

Written from the published architecture (hf:deepseek-ai/DeepSeek-V2,
``modeling_deepseek.py``; arXiv:2405.04434), not from the program's
modules: RMSNorm pre-norm blocks; Multi-head Latent Attention in its
expanded form (query through the ``q_lora`` bottleneck, keys and values
up-projected from the normed ``kv_lora`` latent, one rotary key shared by
all heads, causal softmax at ``(nope + rope)^-0.5 * mscale^2``); YaRN rope;
layer 0 a dense SwiGLU MLP; the other layers a MoE whose float32 gate takes
a softmax over every routed expert, keeps the ``topk_group`` groups with
the best single expert and the top ``top_k`` experts within them, scales
the unnormalised weights by ``routed_scaling_factor`` and adds the shared
experts; a final RMSNorm and an untied head.  Every matrix product runs
under ``jax.default_matmul_precision("highest")``; there is no kernel,
cache or batching.

Departures from the published code, each one a choice of layout or of
deployment and none of arithmetic:

* Rope order.  The published rope de-interleaves each head's 64 rope
  channels (pairs ``(2i, 2i+1)`` become ``(i, i + 32)``) before rotating
  halves.  That is a fixed permutation of the ``q_rope`` and ``k_rope``
  weight columns, and the scores are unchanged under it; this reference
  and the program keep the half-split order, so their weights are the
  published ones with those columns permuted.
* Held share.  ``cfg.expert_first`` / ``cfg.experts_held`` say which
  routed experts this chip holds, as one chip of an expert-parallel
  deployment (the benchmark's: group 0 of 8).  The gate still scores all
  ``n_experts``; only the held experts' contributions are added, and what
  the experts held elsewhere would add is left out, as in the program.

The parameters are the program's tree (``repro.models.model_spec``):
``embed`` / ``layer0`` / ``blocks.pos0`` (stacked over the MoE layers) /
``final_norm``, read by name only.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, cfg) -> jax.Array:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    base = cfg.rope_theta

    def corr(rot):
        return (dim * math.log(cfg.yarn_original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    exps = jnp.arange(0, dim, 2, dtype=F32) / dim
    freq_extra = 1.0 / (base ** exps)
    freq_inter = 1.0 / (cfg.yarn_factor * base ** exps)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def _rope(x, pos, cfg):
    """x (T, heads, d); half-split rotation (see the module docstring)."""
    d = x.shape[-1]
    if cfg.yarn_factor:
        inv = yarn_inv_freq(d, cfg)
        m = (yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    else:
        inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
        m = 1.0
    ang = pos[:, None].astype(F32) * inv[None, :]
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * m)[:, None, :]
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * m)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mla(p, h, cfg):
    """Expanded MLA over the whole sequence h (T, D)."""
    t = h.shape[0]
    nh, nope, rope, vd = (cfg.n_heads, cfg.qk_nope_head_dim,
                          cfg.qk_rope_head_dim, cfg.v_head_dim)
    kvl = cfg.kv_lora_rank
    pos = jnp.arange(t)
    q = _rms(h @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    q = q.reshape(t, nh, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, cfg)
    kv_a = h @ p["wkv_a"]
    c_kv = _rms(kv_a[:, :kvl], p["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kv_a[:, None, kvl:], pos, cfg)                # (T, 1, rope)
    k_nope = (c_kv @ p["wk_b"]).reshape(t, nh, nope)
    v = (c_kv @ p["wv_b"]).reshape(t, nh, vd)
    scale = (nope + rope) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        scale = scale * m * m
    s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
         + jnp.einsum("qhd,kd->hqk", q_pe, k_pe[:, 0])) * scale
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(t, nh * vd) @ p["wo"]


def mlp(p, h):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def gate(h, router, cfg) -> Tuple[jax.Array, jax.Array]:
    """(weights (T, k), global expert ids (T, k)) of the published gate."""
    scores = jax.nn.softmax(h @ router, axis=-1)
    t, e = scores.shape
    if cfg.n_group > 1:
        group_scores = scores.reshape(t, cfg.n_group, -1).max(-1)
        _, group_idx = jax.lax.top_k(group_scores, cfg.topk_group)
        group_mask = jnp.zeros((t, cfg.n_group)).at[
            jnp.arange(t)[:, None], group_idx].set(1.0)
        score_mask = jnp.repeat(group_mask, e // cfg.n_group, axis=1)
        scores = jnp.where(score_mask > 0, scores, 0.0)
    w, idx = jax.lax.top_k(scores, cfg.top_k)
    if cfg.top_k > 1 and cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * cfg.routed_scaling_factor
    return w, idx


def moe(p, h, cfg):
    """-> (this share's routed part + shared experts (T, D), each token's
    routed experts (T, k), global ids)."""
    w, idx = gate(h, p["router"], cfg)
    y = jnp.zeros_like(h)
    for j in range(cfg.experts_held):
        hit = idx == cfg.expert_first + j                      # (T, k)
        weight = jnp.where(hit, w, 0.0).sum(-1)                # (T,)
        ep = {k: p[k][j] for k in ("wi", "wg", "wo")}
        y = y + weight[:, None] * mlp(ep, h)
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], h)
    return y, idx


def forward(params, tokens: np.ndarray, cfg) -> Tuple[jax.Array, List]:
    """-> (logits (T, vocab) float32, each MoE layer's routed experts per
    token (T, k), global ids)."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = f32["embed"]["embedding"][jnp.asarray(tokens)]
        layers = [f32["layer0"]] + [
            jax.tree_util.tree_map(lambda a, i=i: a[i], f32["blocks"]["pos0"])
            for i in range(cfg.n_groups)]
        for i, lp in enumerate(layers):
            h = _rms(x, lp["norm1"]["scale"], cfg.norm_eps)
            x = x + mla(lp["block"], h, cfg)
            h = _rms(x, lp["norm2"]["scale"], cfg.norm_eps)
            if i == 0:
                x = x + mlp(lp["mlp"], h)
            else:
                y, experts = moe(lp["mlp"], h, cfg)
                x = x + y
                routed.append(experts)
        x = _rms(x, f32["final_norm"]["scale"], cfg.norm_eps)
        logits = x @ f32["embed"]["lm_head"][:, : cfg.vocab]
    return logits, routed
