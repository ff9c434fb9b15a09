"""Plain float32 forward of a Jamba model, for tests to compare the served
path against.

Written from the published architecture (hf:ai21labs/AI21-Jamba2-3B,
``modeling_jamba.py``; arXiv:2403.19887), not from the program's modules:
RMSNorm pre-norm layers; a layer is attention iff its index ``i`` has
``i % attn_layer_period == attn_layer_offset`` (the config's ``period`` and
the position of its ``attn`` block), every other layer a Mamba-1 mixer; a
SwiGLU MLP after each mixer (``num_experts`` 1); a final RMSNorm and the
tied embedding as the head.

* Mamba-1 (``JambaMambaMixer.slow_forward``): ``in_proj`` to the hidden
  and gate halves; a depthwise causal conv of width ``d_conv`` with its
  bias, then SiLU; ``x_proj`` to ``[dt | B | C]``; ``dt_layernorm``,
  ``b_layernorm`` and ``c_layernorm`` (RMSNorms at ``rms_norm_eps``);
  ``dt_proj`` plus its bias, softplus; ``A = -exp(A_log)``; the
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``,
  ``y_t = C_t . h_t``, one time step after another (``lax.scan``); the
  ``D`` skip; times ``SiLU(gate)``; ``out_proj``.
* Attention (``JambaAttention``): query, key and value projections
  without bias, grouped-query (here one key-value head for every query
  head) and **no positional encoding**; a causal softmax at
  ``head_dim ** -0.5``; ``o_proj``.

Every matrix product runs under ``jax.default_matmul_precision("highest")``;
there is no kernel, cache or batching.  Attention is computed in blocks of
``QUERY_BLOCK`` query rows.

Departures from the published code, each of layout and none of
arithmetic: the conv weight is stored (d_conv, d_inner) where the
published ``Conv1d`` holds (d_inner, 1, d_conv); the recurrence keeps its
state (d_state, d_inner) where the published loop holds (d_inner,
d_state); and every product runs in float32, where the published model
computes in its own dtype and the recurrence in float32.

The parameters are the program's tree (``repro.models.model_spec``):
``embed`` / ``blocks.pos{i}`` (stacked over the layer groups) /
``final_norm``, read by name only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 64


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba(p, h, cfg):
    """The Mamba-1 mixer over the whole sequence h (T, D)."""
    t = h.shape[0]
    di, n, dtr = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    xz = h @ p["in_proj"]
    xs, gate = xz[:, :di], xz[:, di:]
    k = p["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, di), F32), xs])
    conv = sum(xp[j:j + t] * p["conv_w"][j] for j in range(k)) + p["conv_b"]
    xc = jax.nn.silu(conv)
    proj = xc @ p["x_proj"]
    dt = _rms(proj[:, :dtr], p["dt_norm"], cfg.norm_eps)
    b = _rms(proj[:, dtr:dtr + n], p["b_norm"], cfg.norm_eps)
    c = _rms(proj[:, dtr + n:], p["c_norm"], cfg.norm_eps)
    delta = jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])
    a = -jnp.exp(p["a_log"]).T                                 # (N, Di)

    def step(state, inp):
        d_t, x_t, b_t, c_t = inp
        state = jnp.exp(d_t * a) * state + b_t[:, None] * (d_t * x_t)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, ys = jax.lax.scan(step, jnp.zeros((n, di), F32), (delta, xc, b, c))
    y = (ys + xc * p["d_skip"]) * jax.nn.silu(gate)
    return y @ p["out_proj"]


def attention(p, h, cfg):
    """Causal grouped-query attention without positions over h (T, D)."""
    t = h.shape[0]
    nh, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(t, kvh, nh // kvh, hd)
    k = (h @ p["wk"]).reshape(t, kvh, hd)
    v = (h @ p["wv"]).reshape(t, kvh, hd)
    pos = jnp.arange(t)
    out = []
    for i in range(0, t, QUERY_BLOCK):
        rows = pos[i:i + QUERY_BLOCK]
        s = jnp.einsum("qgrd,kgd->grqk", q[i:i + QUERY_BLOCK], k) * hd ** -0.5
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)
        out.append(o.reshape(len(rows), nh * hd))
    return jnp.concatenate(out) @ p["wo"]


def mlp(p, h):
    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wi"])) @ p["wo"]


def forward(params, tokens: np.ndarray, cfg) -> jax.Array:
    """-> logits (T, vocab) float32 at every position of ``tokens``."""
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), params)
    period = cfg.period
    with jax.default_matmul_precision("highest"):
        x = f32["embed"]["embedding"][jnp.asarray(tokens)]
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(
                lambda a, g=i // period: a[g],
                f32["blocks"][f"pos{i % period}"])
            mixer = (attention if cfg.block_pattern[i % period] == "attn"
                     else mamba)
            x = x + mixer(lp["block"], _rms(x, lp["norm1"]["scale"],
                                            cfg.norm_eps), cfg)
            x = x + mlp(lp["mlp"], _rms(x, lp["norm2"]["scale"], cfg.norm_eps))
        x = _rms(x, f32["final_norm"]["scale"], cfg.norm_eps)
        return x @ f32["embed"]["embedding"][: cfg.vocab].T
