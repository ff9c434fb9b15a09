"""Mixture-of-Experts layer: top-k routing, sort-based capacity dispatch, EP.

Production dispatch path (GSPMD/EP-friendly, flop-light):

1. tokens are viewed as (groups, g, D) with groups sharded over ``data`` —
   one routing group per data shard (the Tiny-OpenCL "work-group" of this
   layer, scheduled onto mesh shards exactly like the paper schedules
   work-groups onto CUs);
2. per-group: softmax router → top-k experts/weights per token;
3. **sort-based dispatch**: assignments are ordered by expert id; each
   token's position-in-expert comes from a stable argsort + running index,
   tokens beyond the per-expert capacity ``c`` are dropped (their combine
   weight is zeroed — standard GShard capacity semantics);
4. dispatched activations land in an (E, c, D) buffer per group via a
   one-hit scatter; expert weights are sharded E → ``model`` so GSPMD
   all-to-alls tokens from data shards to expert shards;
5. expert FFN (gated-SiLU) runs batched over its local experts;
6. combine scatters weighted outputs back to token order.

Aux losses: switch-style load-balance loss + router z-loss, returned to the
trainer (summed over scan groups).

Shared experts (deepseek-v2: 2) run densely on every token and add in.

Serving (prefill and decode) takes the dropless path instead,
:func:`apply_moe_serve`: the router is computed in float32 over all
``n_experts``, the top-k rule of :func:`route` picks each token's experts
(deepseek-v2: group-limited greedy, unnormalised, scaled by 16), and the
assignments to the experts this chip holds (``expert_first`` ..
``+ experts_held``) are sorted by expert into tile-aligned runs that the
grouped-matmul kernel (``repro.kernels.moe_gmm``) multiplies, so no token
is dropped and no capacity exists.  The result is this chip's part of the
layer; what experts held elsewhere would add is not computed.  Training
keeps the capacity path: its EP sharding, aux losses and the capacity
drop test use it.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..kernels.common import round_up
from ..kernels.moe_gmm.ops import moe_gmm, row_tile
from .config import ModelConfig
from .layers import act_fn, cdtype
from .params import ParamSpec, dense_spec


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def moe_spec(cfg: ModelConfig, stacked: int = 0) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    e = cfg.n_experts                     # the router keeps every output
    ff = cfg.d_ff_expert or cfg.d_ff

    def expert_w(din, dout, axes):
        shape = (cfg.experts_held, din, dout)
        ax: Tuple = ("expert",) + axes
        if stacked:
            shape = (stacked,) + shape
            ax = ("layers",) + ax
        return ParamSpec(shape, ax, "normal", din ** -0.5)

    out = {
        "router": dense_spec(d, e, ("embed", None), stacked=stacked),
        "wi": expert_w(d, ff, ("embed", "mlp")),
        "wg": expert_w(d, ff, ("embed", "mlp")),
        "wo": expert_w(ff, d, ("mlp", "embed")),
    }
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        out["shared"] = {
            "wi": dense_spec(d, sff, ("embed", "mlp"), stacked=stacked),
            "wg": dense_spec(d, sff, ("embed", "mlp"), stacked=stacked),
            "wo": dense_spec(sff, d, ("mlp", "embed"), stacked=stacked),
        }
    return out


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    """Per-expert slots per routing group (multiple of 8 for TPU tiling)."""
    c = int(group_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------
def route(logits: jax.Array, cfg: ModelConfig):
    """Router logits (T, E) -> (weights (T, k) f32, experts (T, k) int32).

    Softmax over all experts; with ``n_group > 1`` each group is scored by
    its best expert and only the ``topk_group`` best groups stay eligible
    (DeepSeek-V2's ``group_limited_greedy``).  With ``top_k > 1`` and
    ``norm_topk_prob`` the weights are renormalised to sum to 1, otherwise
    scaled by ``routed_scaling_factor`` (the published gate's either/or).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    k = cfg.top_k
    if cfg.n_group > 1:
        t, e = probs.shape
        group_best = probs.reshape(t, cfg.n_group, -1).max(axis=-1)
        _, top_groups = jax.lax.top_k(group_best, cfg.topk_group)
        keep = jax.nn.one_hot(top_groups, cfg.n_group,
                              dtype=jnp.int32).sum(axis=1) > 0
        keep = jnp.repeat(keep, e // cfg.n_group, axis=1)
        top_w, top_e = jax.lax.top_k(jnp.where(keep, probs, 0.0), k)
    else:
        top_w, top_e = jax.lax.top_k(probs, k)
    if k > 1 and cfg.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    else:
        top_w = top_w * cfg.routed_scaling_factor
    return top_w, top_e


# ---------------------------------------------------------------------------
# Routing + dispatch (per group, vmapped)
# ---------------------------------------------------------------------------
def _route_group(x: jax.Array, logits: jax.Array, cfg: ModelConfig, c: int):
    """x (g, D), logits (g, E) -> dispatched (E*c, D), combine info.

    Returns (buf (E*c, D), slot (g*k,), weight (g*k,), aux (2,)).
    ``slot == E*c`` marks dropped assignments (scattered to a dummy row).
    """
    g, d = x.shape
    e, k = cfg.n_experts, cfg.top_k

    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = route(logits, cfg)                            # (g, k)

    flat_e = top_e.reshape(-1)                                   # (g*k,)
    flat_w = top_w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(g, dtype=jnp.int32), k)

    # position-in-expert via stable sort by expert id
    order = jnp.argsort(flat_e, stable=True)                     # (g*k,)
    sorted_e = flat_e[order]
    # index within the sorted run of each expert
    counts = jnp.bincount(flat_e, length=e)                      # (e,)
    starts = jnp.cumsum(counts) - counts                         # (e,)
    pos_sorted = jnp.arange(g * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)   # unsort

    kept = pos < c
    slot = jnp.where(kept, flat_e * c + pos, e * c)              # dummy last
    weight = jnp.where(kept, flat_w, 0.0)

    buf = jnp.zeros((e * c + 1, d), x.dtype)
    buf = buf.at[slot].add(x[flat_tok])                          # one-hit
    # load-balance loss (Switch): E * sum_e fraction_tokens_e * mean_prob_e
    frac_tok = counts.astype(jnp.float32) / (g * k)
    mean_prob = probs.mean(axis=0)
    lb = e * jnp.sum(frac_tok * mean_prob)
    z = jnp.mean(jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1) ** 2)
    return buf[:-1], slot, weight, flat_tok, jnp.stack([lb, z])


def _combine_group(y: jax.Array, slot, weight, flat_tok, g: int):
    """y (E*c, D) -> (g, D) weighted combine (scatter-add over tokens)."""
    yk = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)], axis=0)
    gathered = yk[slot] * weight[:, None].astype(y.dtype)        # (g*k, D)
    out = jnp.zeros((g, y.shape[1]), y.dtype).at[flat_tok].add(gathered)
    return out


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------
def apply_moe(p, x: jax.Array, cfg: ModelConfig):
    """x (B, S, D) -> (y (B, S, D), aux_losses (2,) [load_balance, z]).

    One routing group per sequence, keeping groups aligned with the batch
    sharding so dispatch scatters stay local.
    """
    b, s, d = x.shape
    e = cfg.n_experts
    if cfg.experts_held != e:
        raise ValueError(f"{cfg.name}: training dispatch computes every "
                         f"expert; {cfg.experts_held} of {e} are held")
    dt = cdtype(cfg)
    n_groups, g = b, s
    c = capacity(cfg, g)

    xg = x.reshape(n_groups, g, d)
    xg = constrain(xg, "batch", None, None)
    logits = jnp.einsum("ngd,de->nge", xg.astype(dt), p["router"].astype(dt))

    dispatch = jax.vmap(lambda xx, ll: _route_group(xx, ll, cfg, c))
    buf, slot, weight, flat_tok, aux = dispatch(xg, logits)
    # buf: (n_groups, E*c, D) -> expert-major for EP
    he = buf.reshape(n_groups, e, c, d)
    he = constrain(he, "batch", "expert", None, None)   # all-to-all boundary

    wi, wg, wo = (p["wi"].astype(dt), p["wg"].astype(dt), p["wo"].astype(dt))
    hidden = act_fn(cfg)(jnp.einsum("necd,edf->necf", he.astype(dt), wg))
    hidden = hidden * jnp.einsum("necd,edf->necf", he.astype(dt), wi)
    y_exp = jnp.einsum("necf,efd->necd", hidden, wo)
    y_exp = constrain(y_exp, "batch", "expert", None, None)

    combine = jax.vmap(lambda yy, sl, w, tk: _combine_group(yy, sl, w, tk, g))
    y = combine(y_exp.reshape(n_groups, e * c, d), slot, weight, flat_tok)
    y = y.reshape(b, s, d)

    if cfg.n_shared_experts:
        sp = p["shared"]
        h = act_fn(cfg)(jnp.dot(x.astype(dt), sp["wg"].astype(dt)))
        h = h * jnp.dot(x.astype(dt), sp["wi"].astype(dt))
        y = y + jnp.dot(h, sp["wo"].astype(dt))

    return y, aux.mean(axis=0)


# ---------------------------------------------------------------------------
# Serving: dropless, over the held experts, through the grouped matmul
# ---------------------------------------------------------------------------
def serve_row_tile(cfg: ModelConfig, tokens: int) -> int:
    """The grouped matmul's row tile for a layer call over ``tokens``
    tokens (each held expert expects ``tokens * top_k / n_experts`` rows)."""
    return row_tile(tokens * cfg.top_k / cfg.n_experts)


def _moe_tokens(p, x: jax.Array, cfg: ModelConfig, layer):
    """x (N, D) -> (routed + shared output (N, D), each token's routed
    experts (N, top_k) int32, global ids).  ``p``'s expert weights may be
    stacked over layers (L, E_held, ...), ``layer`` picking this one."""
    n, d = x.shape
    dt = cdtype(cfg)
    eh, k = cfg.experts_held, cfg.top_k
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weight, expert = route(logits, cfg)                          # (N, k)
    local = expert - cfg.expert_first
    held = (local >= 0) & (local < eh)
    onehot = jax.nn.one_hot(jnp.where(held, local, eh).reshape(-1), eh,
                            dtype=jnp.int32)                     # (N*k, eh)
    counts = onehot.sum(axis=0)                                  # (eh,)
    # expert e's rows start at a multiple of the tile and fill whole tiles
    tm = serve_row_tile(cfg, n)
    padded = -(-counts // tm) * tm
    start = jnp.cumsum(padded) - padded
    rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(axis=1) - 1
    rows = round_up(n * min(k, eh) + min(eh, n * k) * (tm - 1), tm)
    flat_held = held.reshape(-1)
    dest = jnp.where(flat_held, start[jnp.where(flat_held, local.reshape(-1),
                                                0)] + rank, rows)
    token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    xs = x.astype(dt)[token]                                     # (rows, D)

    wi, wg, wo = (p["wi"].astype(dt), p["wg"].astype(dt), p["wo"].astype(dt))
    h = act_fn(cfg)(moe_gmm(xs, wg, padded, layer, tm=tm))
    h = h * moe_gmm(xs, wi, padded, layer, tm=tm)
    y_rows = moe_gmm(h, wo, padded, layer, tm=tm)                # (rows, D)

    dest = jnp.minimum(dest.reshape(n, k), rows - 1)
    y = jnp.zeros((n, d), jnp.float32)
    for j in range(k):
        y = y + jnp.where(held[:, j, None],
                          y_rows[dest[:, j]].astype(jnp.float32)
                          * weight[:, j, None], 0.0)
    y = y.astype(dt)
    if cfg.n_shared_experts:
        sp = p["shared"]
        hs = act_fn(cfg)(jnp.dot(x.astype(dt), sp["wg"].astype(dt)))
        hs = hs * jnp.dot(x.astype(dt), sp["wi"].astype(dt))
        y = y + jnp.dot(hs, sp["wo"].astype(dt))
    return y, expert


def apply_moe_serve(p, x: jax.Array, cfg: ModelConfig, layer=0):
    """x (B, S, D) -> (y (B, S, D), each token's routed experts (B, S,
    top_k) int32, global ids).  The expert weights ``wi`` / ``wg`` / ``wo``
    may be the whole stack over layers, ``layer`` picking this one (the
    kernel then reads that layer's touched experts in place).  All B * S
    tokens go through ONE grouped matmul — a decode step's B slots as a
    prefill's S prompt tokens — so an expert that several tokens route to
    has its weights read once; each token still gets its own routing."""
    y, expert = _moe_tokens(p, x.reshape(-1, x.shape[-1]), cfg,
                            jnp.asarray(layer, jnp.int32))
    return y.reshape(x.shape), expert.reshape(x.shape[:-1] + (-1,))
