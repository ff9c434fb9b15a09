"""Plain float32 forward of DeepSeek-V2 as one chip of its expert-parallel
deployment.

Written from the published architecture (hf:deepseek-ai/DeepSeek-V2,
``modeling_deepseek``; arXiv:2405.04434), not from the program under test,
and importing nothing from it: pre-norm RMSNorm blocks; Multi-head Latent
Attention in its expanded form (the query through the ``q_lora``
bottleneck and its norm; keys and values up-projected from the normed
``kv_lora`` latent; one rotary key of ``qk_rope_head_dim`` shared by every
head; causal softmax at ``(nope + rope)^-0.5`` times YaRN's
``mscale(factor, mscale_all_dim)^2``); YaRN rope (inverse frequencies
blended between ``base^(-2i/d)`` and that over ``factor`` by a linear ramp
over the correction range of ``beta_fast`` and ``beta_slow``); layer 0 a
dense SwiGLU MLP; the others a MoE: a float32 gate, softmax over all
``router_experts``, the ``topk_group`` groups with the best single expert
eligible, the top ``num_experts_per_tok`` experts among them, weights not
renormalised but scaled by ``routed_scaling_factor``, plus the shared
experts; a final RMSNorm and an untied head.  Every matrix product runs
under ``jax.default_matmul_precision("highest")``.  Attention is computed
in blocks of ``QUERY_BLOCK`` query rows so that a 12544-position sequence
fits beside the weights.

Departures, each a choice of layout or of deployment:

* The published rope de-interleaves each head's rope channels before
  rotating halves; that is a fixed permutation of the ``q_rope`` and
  ``k_rope`` weight columns, under which the scores are unchanged, so the
  weights here are read in the half-split order.
* The chip holds routed experts ``first_held_expert`` ..
  ``+ n_routed_experts`` of the router's ``router_experts``: only their
  part of each MoE layer is added, as on one chip of the deployment.

The weights are the benchmark's own (``chipbench.families.deepseek_v2``):
``embed`` (V, d), ``head`` (d, V), ``ln_f``; ``dense`` (layer 0) and
``moe`` (the MoE layers, stacked) with the attention's ``ln1``, ``wq_a``,
``q_norm``, ``wq_b``, ``wkv_a``, ``kv_norm``, ``wk_b``, ``wv_b``, ``wo``,
``ln2``; ``dense`` adds ``gate``, ``up``, ``down``; ``moe`` adds
``router``, the held experts' ``gate_e``, ``up_e``, ``down_e`` (experts,
in, out) and the shared ones' ``gate_s``, ``up_s``, ``down_s``.

Routing replay.  A token whose routing is decided within rounding (two
experts' or two groups' scores nearly equal) may route differently in a
bfloat16 program than here, and scaled by ``routed_scaling_factor`` one
expert more or less moves its hidden state by about its own size.  So the
forward can follow a given routing (``routes``: each MoE layer's experts
per position, as the program chose them) while computing every weight
itself, and reads at each position how far that routing lies from the one
it would choose (``route_margin``, in router-logit units).  The logits are
then compared under one routing, and the routing is judged on its own.

``control=True`` computes the same forward with both operands of every
matrix product rounded to float8 (e4m3, scaled per row of the left operand
and per column of the right one), the next precision below the bfloat16
the configuration states.  The correctness check must fail it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0
QUERY_BLOCK = 64
#: logit rows computed at once
ROW_WINDOW = 256
F32 = jnp.float32


class Dims(NamedTuple):
    heads: int
    nope: int
    rope: int
    v: int
    kv_lora: int
    eps: float
    theta: float
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    top_k: int
    n_group: int
    topk_group: int
    router_experts: int
    first_held: int
    held: int
    scaling: float
    norm_topk: bool


def dims(cfg: dict) -> Dims:
    rs = cfg["rope_scaling"]
    return Dims(
        heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        kv_lora=cfg["kv_lora_rank"], eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"]), factor=float(rs["factor"]),
        original=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], router_experts=cfg["router_experts"],
        first_held=cfg["first_held_expert"], held=cfg["n_routed_experts"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]))


def round_e4m3(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to float8 e4m3 (3 mantissa bits, subnormals below
    2**-6) after scaling its largest magnitude along ``axis`` to 448."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    y = x * scale
    a = jnp.abs(y)
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9))), -6.0)
    step = 2.0 ** (e - 3.0)
    return jnp.sign(y) * jnp.round(a / step) * step / scale


def _mm(a, b, control: bool):
    b = b.astype(F32)
    if control:
        a, b = round_e4m3(a, -1), round_e4m3(b, -2)
    return a @ b


def _ein(eq: str, a, b, a_axis: int, b_axis: int, control: bool):
    """A batched product; ``*_axis`` is each operand's contracted axis."""
    if control:
        a, b = round_e4m3(a, a_axis), round_e4m3(b, b_axis)
    return jnp.einsum(eq, a, b)


def _rms(x, w, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(F32))


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, m: Dims) -> jax.Array:
    def corr(rot):
        return (dim * math.log(m.original / (rot * 2 * math.pi))
                / (2 * math.log(m.theta)))

    low = max(math.floor(corr(m.beta_fast)), 0)
    high = min(math.ceil(corr(m.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    exps = jnp.arange(0, dim, 2, dtype=F32) / dim
    extra = 1.0 / m.theta ** exps
    inter = 1.0 / (m.factor * m.theta ** exps)
    mask = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                          / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def _rope(x, pos, m: Dims):
    """x (T, heads, d), rotated by halves."""
    d = x.shape[-1]
    scale = (yarn_get_mscale(m.factor, m.mscale)
             / yarn_get_mscale(m.factor, m.mscale_all_dim))
    ang = pos[:, None].astype(F32) * yarn_inv_freq(d, m)[None, :]
    cos = (jnp.concatenate([jnp.cos(ang)] * 2, -1) * scale)[:, None]
    sin = (jnp.concatenate([jnp.sin(ang)] * 2, -1) * scale)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(x, w, m: Dims, control: bool):
    """x + MLA(x) over the whole sequence x (T, d)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, w["ln1"], m.eps)
    q = _mm(_rms(_mm(h, w["wq_a"], control), w["q_norm"], m.eps),
            w["wq_b"], control).reshape(t, m.heads, m.nope + m.rope)
    q_nope, q_pe = q[..., : m.nope], _rope(q[..., m.nope:], pos, m)
    kv_a = _mm(h, w["wkv_a"], control)
    c_kv = _rms(kv_a[:, : m.kv_lora], w["kv_norm"], m.eps)
    k_pe = _rope(kv_a[:, None, m.kv_lora:], pos, m)[:, 0]       # (T, rope)
    k_nope = _mm(c_kv, w["wk_b"], control).reshape(t, m.heads, m.nope)
    v = _mm(c_kv, w["wv_b"], control).reshape(t, m.heads, m.v)
    scale = ((m.nope + m.rope) ** -0.5
             * yarn_get_mscale(m.factor, m.mscale_all_dim) ** 2)
    qb = min(QUERY_BLOCK, t)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * qb, qb)
        s = (_ein("qhd,khd->hqk", qn, k_nope, -1, -1, control)
             + _ein("qhd,kd->hqk", qp, k_pe, -1, -1, control)) * scale
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("hqk,khd->qhd", p, v, -1, 0, control).reshape(qb, -1)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, -1)
    return x + _mm(o, w["wo"], control)


def _swiglu(h, gate, up, down, control):
    return _mm(jax.nn.silu(_mm(h, gate, control)) * _mm(h, up, control),
               down, control)


def gate(logits, m: Dims):
    """Global expert ids (T, k) the published gate picks from router
    logits (T, E)."""
    scores = jax.nn.softmax(logits, axis=-1)
    t = scores.shape[0]
    if m.n_group > 1:
        group_scores = scores.reshape(t, m.n_group, -1).max(-1)
        _, group_idx = jax.lax.top_k(group_scores, m.topk_group)
        group_mask = jnp.zeros((t, m.n_group)).at[
            jnp.arange(t)[:, None], group_idx].set(1.0)
        keep = jnp.repeat(group_mask, m.router_experts // m.n_group, axis=1)
        scores = jnp.where(keep > 0, scores, 0.0)
    return jax.lax.top_k(scores, m.top_k)[1]


def gate_weights(logits, idx, m: Dims):
    """The published gate's weights (T, k) of experts ``idx`` (T, k)."""
    w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
    if m.top_k > 1 and m.norm_topk:
        return w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * m.scaling


def route_margin(logits, used, m: Dims):
    """How far routing ``used`` (T, k) lies from the gate's own choice on
    router logits (T, E), per token, in logit units; 0 where the gate would
    choose it.  Two parts, the larger counts: a group ``used`` draws from
    that the gate does not keep, by how far its best logit lies below the
    ``topk_group``-th group's; and among the experts of the groups
    ``used`` kept (its own groups, filled up with the gate's best others),
    by how far the best expert left out lies above the worst taken."""
    t, e = logits.shape
    size = e // m.n_group
    groups = logits.reshape(t, m.n_group, size).max(-1)            # (T, G)
    mine = jax.nn.one_hot(used // size, m.n_group).max(1) > 0       # (T, G)
    last = jax.lax.top_k(groups, m.topk_group)[0][:, -1:]
    group_gap = jnp.max(jnp.where(mine, last - groups, 0.0), axis=-1)
    _, kept = jax.lax.top_k(jnp.where(mine, jnp.inf, groups), m.topk_group)
    eligible = jnp.repeat(jax.nn.one_hot(kept, m.n_group).max(1) > 0, size,
                          axis=1)
    taken = jax.nn.one_hot(used, e).max(1) > 0
    left = jnp.max(jnp.where(eligible & ~taken, logits, -jnp.inf), axis=-1)
    worst = jnp.min(jnp.take_along_axis(logits, used, axis=-1), axis=-1)
    return jnp.maximum(jnp.maximum(group_gap, left - worst), 0.0)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _dense_layer(x, w, *, m: Dims, control: bool):
    x = _attention(x, w, m, control)
    h = _rms(x, w["ln2"], m.eps)
    return x + _swiglu(h, w["gate"], w["up"], w["down"], control)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _moe_layer(x, stacked, i, routes, n, *, m: Dims, control: bool):
    """-> (x after MLA and this chip's MoE part, the gate's own experts
    (T, k), route margins (T,)).  Positions below ``n`` take experts
    ``routes`` (T, k) and read their margin; the others take the gate's
    own (margin 0)."""
    w = {k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
         for k, a in stacked.items()}
    x = _attention(x, w, m, control)
    h = _rms(x, w["ln2"], m.eps)
    logits = _mm(h, w["router"], control)
    own = gate(logits, m)
    replay = (jnp.arange(x.shape[0]) < n)[:, None]
    idx = jnp.where(replay, routes, own)
    weight = gate_weights(logits, idx, m)
    margin = jnp.where(replay[:, 0], route_margin(logits, idx, m), 0.0)

    def expert(y, j):
        wj = jnp.where(idx == m.first_held + j, weight, 0.0).sum(-1)
        out = _swiglu(h, w["gate_e"][j], w["up_e"][j], w["down_e"][j],
                      control)
        return y + wj[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(m.held))
    y = y + _swiglu(h, w["gate_s"], w["up_s"], w["down_s"], control)
    return x + y, own, margin


@functools.partial(jax.jit, static_argnames=("window", "vocab", "eps",
                                             "control"))
def _logits(x, start, ln_f, head, *, window, vocab, eps, control):
    rows = jax.lax.dynamic_slice_in_dim(x, start, window)
    return _mm(_rms(rows, ln_f, eps), head[:, :vocab], control)


def hidden(weights: dict, cfg: dict, tokens: np.ndarray,
           control: bool = False, routes=None):
    """(final hidden states (T, d), the gate's own experts per MoE layer
    (L, T, k), route margins (L, T)).  ``routes`` (L, n, k), if given,
    are the experts the first ``n`` positions take (see the module
    docstring)."""
    m = dims(cfg)
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    t = len(tokens)
    given = np.zeros((n_moe, t, m.top_k), np.int32)
    n = 0
    if routes is not None:
        n = routes.shape[1]
        given[:, :n] = routes
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(F32)
        x = _dense_layer(x, weights["dense"], m=m, control=control)
        own, margins = [], []
        for i in range(n_moe):
            x, e, margin = _moe_layer(x, weights["moe"], i, given[i], n,
                                      m=m, control=control)
            own.append(e)
            margins.append(margin)
    return x, jnp.stack(own), jnp.stack(margins)


def logits_at(weights: dict, cfg: dict, x, first: int, n: int,
              control: bool = False) -> jax.Array:
    """Logits (n, vocab) of positions ``first .. first + n - 1``."""
    out = []
    window = min(ROW_WINDOW, x.shape[0])
    with jax.default_matmul_precision("highest"):
        for a in range(first, first + n, window):
            start = min(a, x.shape[0] - window)
            rows = _logits(x, start, weights["ln_f"], weights["head"],
                           window=window, vocab=cfg["vocab_size"],
                           eps=float(cfg["rms_norm_eps"]), control=control)
            out.append(rows[a - start: a - start + min(window,
                                                       first + n - a)])
    return jnp.concatenate(out)


def _gap(ref, chosen):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return float(jnp.max(best - got))


def served_gap(weights: dict, cfg: dict, prompt: np.ndarray,
               served: np.ndarray, routes: np.ndarray, length: int,
               control: bool = False) -> tuple:
    """((logit gap, route margin) of the served tokens, the same for the
    control or None).

    The sequence prompt + served[:-1] (zero-padded to ``length``, a
    multiple of ``QUERY_BLOCK`` or below it; causal, so the padding changes
    no earlier row) runs through the reference with its positions routed
    as ``routes`` (L, len(prompt) + len(served) - 1, k) says, the experts
    the program chose.  Served token ``j`` was chosen from the logits at
    position ``len(prompt) - 1 + j``; the logit gap is the widest by which
    a served token's reference logit lies below the reference's best, and
    the route margin the widest of ``route_margin`` over every position and
    MoE layer.  The control reads both for its own first choices and its
    own routing, each replayed through the float32 reference the same way.
    """
    seq = np.zeros(length, np.int32)
    n = len(prompt) + len(served) - 1
    seq[:n] = np.concatenate([prompt, served[:-1]])
    first = len(prompt) - 1

    def judged(routes, chosen):
        x, _, margins = hidden(weights, cfg, seq, routes=routes)
        ref = logits_at(weights, cfg, x, first, len(served))
        return _gap(ref, chosen), float(jnp.max(margins))

    if routes.shape[1] < n:
        raise ValueError(f"routes cover {routes.shape[1]} positions of {n}")
    program = judged(np.asarray(routes)[:, :n],
                     jnp.asarray(served, jnp.int32))
    ctl = None
    if control:
        xl, own, _ = hidden(weights, cfg, seq, control=True)
        low = logits_at(weights, cfg, xl, first, len(served), control=True)
        del xl
        ctl = judged(np.asarray(own)[:, :n],
                     jnp.argmax(low, axis=-1).astype(jnp.int32))
    return program, ctl
