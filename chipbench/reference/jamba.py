"""Plain float32 forward of a Jamba model (AI21-Jamba2-3B).

Written from the published architecture (hf:ai21labs/AI21-Jamba2-3B,
``modeling_jamba``; arXiv:2403.19887), not from the program under test,
and importing nothing from it: pre-norm RMSNorm layers, layer ``i`` an
attention layer iff ``i % attn_layer_period == attn_layer_offset`` and a
Mamba-1 mixer otherwise, each followed by a SwiGLU MLP
``down(silu(gate(x)) * up(x))`` (``num_experts`` 1); a final RMSNorm; the
tied embedding as the head.

* Mamba-1 (``JambaMambaMixer``): ``in_proj`` to the hidden and gate
  halves; a depthwise causal conv of width ``mamba_d_conv`` plus its bias,
  then SiLU; ``x_proj`` to ``[dt | B | C]``; the ``dt``, ``B`` and ``C``
  RMSNorms; ``dt_proj`` plus its bias, softplus; ``A = -exp(A_log)``; the
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t = C_t .
  h_t`` one time step after another (a sequential ``lax.scan``); the ``D``
  skip; times ``SiLU(gate)``; ``out_proj``.
* Attention (``JambaAttention``): q, k, v and o projections without bias;
  ``num_key_value_heads`` key-value heads, each read by its group of query
  heads; no positional encoding; a causal softmax at ``head_dim ** -0.5``,
  computed in blocks of ``QUERY_BLOCK`` query rows so that a 33280-token
  sequence fits beside the weights.

Every matrix product runs under ``jax.default_matmul_precision("highest")``,
so a TPU computes it in float32 and not in one pass of bfloat16.  The
logits are computed only at the positions asked for, ``ROW_WINDOW`` rows
at a time.

Departures, each of layout and none of arithmetic: the conv weight is held
(d_conv, d_inner), the published ``Conv1d`` (d_inner, 1, d_conv); the
recurrence's state (d_state, d_inner), the published loop's (d_inner,
d_state); everything in float32, where the published model computes in
its own dtype and only the recurrence in float32.

The weights are the benchmark's own (``chipbench.families.jamba``):
``embed`` (V, d), ``ln_f``, and ``pos{p}`` for each position ``p`` of the
period, each leaf stacked over the groups of layers (layer ``i`` is
position ``i % attn_layer_period`` of group ``i // attn_layer_period``):
a Mamba position's ``ln1``, ``in_proj``, ``conv_w``, ``conv_b``,
``x_proj``, ``dt_norm``, ``b_norm``, ``c_norm``, ``dt_proj``,
``dt_bias``, ``a_log``, ``d``, ``out_proj``; the attention position's
``ln1``, ``wq``, ``wk``, ``wv``, ``wo``; and every position's ``ln2``,
``gate``, ``up``, ``down``.

``control=True`` computes the same forward with both operands of every
matrix product rounded to float8 (e4m3, scaled per row of the left operand
and per column of the right one), the next precision below the bfloat16
the configuration states.  The correctness check must fail it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 448.0
QUERY_BLOCK = 256
#: logit rows computed at once
ROW_WINDOW = 256
F32 = jnp.float32


class Dims(NamedTuple):
    heads: int
    kv_heads: int
    eps: float
    d_state: int
    dt_rank: int
    period: int
    offset: int


def dims(cfg: dict) -> Dims:
    return Dims(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                eps=float(cfg["rms_norm_eps"]), d_state=cfg["mamba_d_state"],
                dt_rank=cfg["mamba_dt_rank"],
                period=cfg["attn_layer_period"],
                offset=cfg["attn_layer_offset"])


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


def _mlp(x, w, m: Dims, control: bool):
    h = _rms(x, w["ln2"], m.eps)
    return x + _mm(jax.nn.silu(_mm(h, w["gate"], control))
                   * _mm(h, w["up"], control), w["down"], control)


def _mamba(x, w, m: Dims, control: bool):
    """x + Mamba(x) over the whole sequence x (T, d)."""
    t = x.shape[0]
    di = w["d"].shape[0]
    h = _rms(x, w["ln1"], m.eps)
    xz = _mm(h, w["in_proj"], control)
    xs, gate = xz[:, :di], xz[:, di:]
    conv_w = w["conv_w"].astype(F32)
    k = conv_w.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), F32), xs])
    conv = sum(xp[j:j + t] * conv_w[j] for j in range(k))
    xc = jax.nn.silu(conv + w["conv_b"].astype(F32))
    proj = _mm(xc, w["x_proj"], control)
    n, r = m.d_state, m.dt_rank
    dt = _rms(proj[:, :r], w["dt_norm"], m.eps)
    b = _rms(proj[:, r:r + n], w["b_norm"], m.eps)
    c = _rms(proj[:, r + n:], w["c_norm"], m.eps)
    delta = jax.nn.softplus(_mm(dt, w["dt_proj"], control)
                            + w["dt_bias"].astype(F32))
    a = -jnp.exp(w["a_log"].astype(F32)).T                    # (N, Di)

    def step(state, inp):
        d_t, x_t, b_t, c_t = inp
        state = jnp.exp(d_t * a) * state + b_t[:, None] * (d_t * x_t)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, ys = jax.lax.scan(step, jnp.zeros((n, di), F32), (delta, xc, b, c))
    y = (ys + xc * w["d"].astype(F32)) * jax.nn.silu(gate)
    return x + _mm(y, w["out_proj"], control)


def _attention(x, w, m: Dims, control: bool):
    """x + attention(x) over the whole sequence x (T, d), no positions."""
    t = x.shape[0]
    hd = w["wq"].shape[1] // m.heads
    rep = m.heads // m.kv_heads
    h = _rms(x, w["ln1"], m.eps)
    q = _mm(h, w["wq"], control).reshape(t, m.kv_heads, rep, hd)
    k = _mm(h, w["wk"], control).reshape(t, m.kv_heads, hd)
    v = _mm(h, w["wv"], control).reshape(t, m.kv_heads, hd)
    pos = jnp.arange(t)
    qb = min(QUERY_BLOCK, t)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = _ein("qgrd,kgd->grqk", qi, k, -1, -1, control) * hd ** -0.5
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _ein("grqk,kgd->qgrd", p, v, -1, 0, control).reshape(qb, -1)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, -1)
    return x + _mm(o, w["wo"], control)


@functools.partial(jax.jit, static_argnames=("m", "attn", "control"))
def _layer(x, stacked, g, *, m: Dims, attn: bool, control: bool):
    """Layer ``g`` of a position's stack over the whole sequence x (T, d)."""
    w = {k: jax.lax.dynamic_index_in_dim(a, g, keepdims=False)
         for k, a in stacked.items()}
    x = (_attention if attn else _mamba)(x, w, m, control)
    return _mlp(x, w, m, control)


@functools.partial(jax.jit, static_argnames=("window", "vocab", "eps",
                                             "control"))
def _logits(x, start, ln_f, embed, *, window, vocab, eps, control):
    rows = jax.lax.dynamic_slice_in_dim(x, start, window)
    return _mm(_rms(rows, ln_f, eps), embed[:vocab].T, control)


def hidden(weights: dict, cfg: dict, tokens: np.ndarray,
           control: bool = False) -> jax.Array:
    """Final hidden states (T, d), before the last norm."""
    m = dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(tokens)].astype(F32)
        for i in range(cfg["num_hidden_layers"]):
            p, g = i % m.period, i // m.period
            x = _layer(x, weights[f"pos{p}"], g, m=m, attn=p == m.offset,
                       control=control)
    return x


def logits_at(weights: dict, cfg: dict, x, first: int, n: int,
              control: bool = False) -> jax.Array:
    """Logits (n, vocab) of positions ``first .. first + n - 1``."""
    out = []
    window = min(ROW_WINDOW, x.shape[0])
    with jax.default_matmul_precision("highest"):
        for a in range(first, first + n, window):
            start = min(a, x.shape[0] - window)
            rows = _logits(x, start, weights["ln_f"], weights["embed"],
                           window=window, vocab=cfg["vocab_size"],
                           eps=float(cfg["rms_norm_eps"]), control=control)
            out.append(rows[a - start: a - start + min(window,
                                                       first + n - a)])
    return jnp.concatenate(out)


def logits(weights: dict, cfg: dict, tokens: np.ndarray,
           control: bool = False) -> jax.Array:
    """Logits (T, vocab) at every position of ``tokens``."""
    x = hidden(weights, cfg, tokens, control)
    return logits_at(weights, cfg, x, 0, len(tokens), control)


def _gap(ref, chosen):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return float(jnp.max(best - got))


def served_gap(weights: dict, cfg: dict, prompt: np.ndarray,
               served: np.ndarray, length: int,
               control: bool = False) -> tuple:
    """(widest gap of the served tokens, widest gap of the control's own
    first choices or None) over the positions that produced ``served``.

    The sequence prompt + served[:-1], zero-padded to ``length`` (a
    multiple of ``QUERY_BLOCK``, or below it; causal, so the padding
    changes no earlier row), runs through the reference; served token
    ``j`` was chosen from the logits at position ``len(prompt) - 1 + j``.
    """
    seq = np.zeros(length, np.int32)
    n = len(prompt) + len(served) - 1
    seq[:n] = np.concatenate([prompt, served[:-1]])
    first = len(prompt) - 1
    ref = logits_at(weights, cfg, hidden(weights, cfg, seq), first,
                    len(served))
    gap = _gap(ref, jnp.asarray(served, jnp.int32))
    ctl = None
    if control:
        xl = hidden(weights, cfg, seq, control=True)
        low = logits_at(weights, cfg, xl, first, len(served), control=True)
        del xl
        ctl = _gap(ref, jnp.argmax(low, axis=-1).astype(jnp.int32))
    return gap, ctl
