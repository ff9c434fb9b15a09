"""Plain float32 forward of a Qwen2 decoder (the Qwen2.5 family).

Written from the published architecture (hf:Qwen/Qwen2.5-3B,
``modeling_qwen2``), not from the program under test: pre-norm RMSNorm
blocks; grouped-query attention with biases on q, k and v, rotary
embedding on the two halves of each head (``rotate_half``) and a causal
softmax; a SwiGLU MLP ``down(silu(gate(x)) * up(x))``; a final RMSNorm;
logits from the tied embedding.  Every matrix product runs under
``jax.default_matmul_precision("highest")``, so a TPU computes it in
float32 and not in one pass of bfloat16.

The weights are the benchmark's own (``chipbench.families.qwen2``), in
its flat layout: ``embed`` (V, d); per layer, stacked over the layers,
``ln1``, ``wq``, ``bq``, ``wk``, ``bk``, ``wv``, ``bv``, ``wo``, ``ln2``,
``gate``, ``up``, ``down``; ``ln_f``.

``control=True`` computes the same forward with both operands of every
matrix product rounded to float8 (e4m3, scaled per row of the left operand
and per column of the right one), the next precision below the bfloat16
the configuration states.  The correctness check must fail it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
              "gate", "up", "down")
E4M3_MAX = 448.0


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
    if control:
        a, b = round_e4m3(a, -1), round_e4m3(b, 0)
    return a @ b


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (T, heads, hd); the two halves of each head rotate together."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "control"))
def _layer(x, stacked, i, *, heads, kv_heads, eps, theta, control):
    """One decoder layer over the whole sequence x (T, d)."""
    w = {k: jax.lax.dynamic_index_in_dim(stacked[k], i, keepdims=False
                                         ).astype(jnp.float32)
         for k in LAYER_KEYS}
    t, d = x.shape
    hd = w["wq"].shape[1] // heads
    pos = jnp.arange(t)
    h = _rms(x, w["ln1"], eps)
    q = (_mm(h, w["wq"], control) + w["bq"]).reshape(t, heads, hd)
    k = (_mm(h, w["wk"], control) + w["bk"]).reshape(t, kv_heads, hd)
    v = (_mm(h, w["wv"], control) + w["bv"]).reshape(t, kv_heads, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=1)           # query head j reads kv head j//rep
    v = jnp.repeat(v, rep, axis=1)
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))   # (heads, T, hd)
    s = jax.vmap(lambda a, b: _mm(a, b.T, control))(qh, kh) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jax.vmap(lambda a, b: _mm(a, b, control))(p, vh)     # (heads, T, hd)
    x = x + _mm(o.transpose(1, 0, 2).reshape(t, heads * hd), w["wo"],
                control)
    h = _rms(x, w["ln2"], eps)
    mlp = jax.nn.silu(_mm(h, w["gate"], control)) * _mm(h, w["up"], control)
    return x + _mm(mlp, w["down"], control)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "control"))
def _logits(x, ln_f, embed, *, vocab, eps, control):
    h = _rms(x, ln_f.astype(jnp.float32), eps)
    return _mm(h, embed[:vocab].astype(jnp.float32).T, control)


def logits(weights: dict, cfg: dict, tokens: np.ndarray, length: int,
           control: bool = False) -> jax.Array:
    """Logits (length, vocab) at every position of ``tokens``, zero-padded
    to ``length`` (causal, so the padding changes no earlier row)."""
    toks = np.zeros(length, np.int32)
    toks[: len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(x, weights["layers"], i,
                       heads=cfg["num_attention_heads"],
                       kv_heads=cfg["num_key_value_heads"],
                       eps=float(cfg["rms_norm_eps"]),
                       theta=float(cfg["rope_theta"]), control=control)
        return _logits(x, weights["ln_f"], weights["embed"],
                       vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
                       control=control)


@jax.jit
def _gaps(ref, chosen, rows):
    """Per row: how far the chosen token's reference logit lies below the
    reference's best (rows outside ``rows`` read 0)."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return jnp.where(rows, best - got, 0.0)


def served_gap(weights: dict, cfg: dict, prompt: np.ndarray,
               served: np.ndarray, length: int,
               control: bool = False) -> tuple:
    """(widest gap of the served tokens, widest gap of the control's own
    first choices or None) over the positions that produced ``served``.

    Served token ``j`` was chosen from the logits at position
    ``len(prompt) - 1 + j`` of the sequence prompt + served[:-1]."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    start = len(prompt) - 1
    chosen = np.zeros(length, np.int32)
    chosen[start: start + len(served)] = served
    rows = np.zeros(length, bool)
    rows[start: start + len(served)] = True
    ref = logits(weights, cfg, seq, length)
    gap = float(jnp.max(_gaps(ref, jnp.asarray(chosen), jnp.asarray(rows))))
    ctl = None
    if control:
        low = logits(weights, cfg, seq, length, control=True)
        pick = jnp.argmax(low, axis=-1).astype(jnp.int32)
        ctl = float(jnp.max(_gaps(ref, pick, jnp.asarray(rows))))
        del low
    del ref
    return gap, ctl
