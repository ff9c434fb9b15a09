"""Mamba (S6) block — the SSM half of Jamba's 1:7 attn:mamba interleave.

Block structure (Mamba-1, as used by Jamba):

    x ->(in_proj) [xz | z] -> causal depthwise conv1d -> SiLU
      ->(x_proj) [dt_low | B | C] -> RMSNorm each (Jamba's dt/B/C norms)
      ; dt = softplus(dt_proj(dt_low) + bias)
      -> selective scan (kernels/mamba_scan) -> * SiLU(z) ->(out_proj) y

Decode keeps two states per layer: the conv window (B, d_conv-1, d_inner)
and the SSM state (B, d_state, d_inner), channels minor so that it is
lane-dense — O(1) in context length, which is why jamba runs the
long_500k cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..kernels.mamba_scan.ops import mamba_scan
from .config import ModelConfig
from .layers import cdtype, rms_norm_1d
from .params import ParamSpec, dense_spec


def mamba_spec(cfg: ModelConfig, stacked: int = 0) -> Dict[str, ParamSpec]:
    d, di = cfg.d_model, cfg.mamba_d_inner
    n, dc, dtr = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank

    def p(shape, axes, init="normal", scale=1.0):
        if stacked:
            shape = (stacked,) + shape
            axes = ("layers",) + axes
        return ParamSpec(shape, axes, init, scale)

    return {
        "in_proj": dense_spec(d, 2 * di, ("embed", "mlp"), stacked=stacked),
        "conv_w": p((dc, di), (None, "mlp"), "normal", dc ** -0.5),
        "conv_b": p((di,), ("mlp",), "zeros"),
        "x_proj": dense_spec(di, dtr + 2 * n, ("mlp", None), stacked=stacked),
        "dt_norm": p((dtr,), (None,), "ones"),
        "b_norm": p((n,), (None,), "ones"),
        "c_norm": p((n,), (None,), "ones"),
        "dt_proj": dense_spec(dtr, di, (None, "mlp"), stacked=stacked),
        "dt_bias": p((di,), ("mlp",), "constant"),     # softplus(0) ~ .69
        # A stored as -exp(a_log) < 0; init a_log = log(arange(1, N+1))
        "a_log": p((di, n), ("mlp", None), "constant"),
        "d_skip": p((di,), ("mlp",), "ones"),
        "out_proj": dense_spec(di, d, ("mlp", "embed"), stacked=stacked),
    }


def _conv1d_causal(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv: x (B, T, Di), w (K, Di) -> (B, T, Di)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(k):                       # K = 4: unrolled, fuses to adds
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None]
    return out + b[None, None]


def _ssm_inputs(p, x: jax.Array, cfg: ModelConfig):
    """Post-conv activations -> (delta, B, C) for the scan; dt_low, B and
    C each pass through their RMSNorm first."""
    n, dtr, eps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.norm_eps
    dt = cdtype(cfg)
    proj = jnp.dot(x.astype(dt), p["x_proj"].astype(dt))
    dt_low, bmat, cmat = jnp.split(proj, [dtr, dtr + n], axis=-1)
    dt_low = rms_norm_1d(dt_low, p["dt_norm"], eps)
    bmat = rms_norm_1d(bmat, p["b_norm"], eps)
    cmat = rms_norm_1d(cmat, p["c_norm"], eps)
    delta = jax.nn.softplus(
        jnp.dot(dt_low.astype(dt), p["dt_proj"].astype(dt)).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    return delta, bmat.astype(jnp.float32), cmat.astype(jnp.float32)


def mamba_full(p, x: jax.Array, cfg: ModelConfig, *,
               return_state: bool = False):
    """x (B, S, D) -> (B, S, D)  [+ (conv_state, ssm_state) for cache]."""
    b, s, _ = x.shape
    di, dc = cfg.mamba_d_inner, cfg.mamba_d_conv
    dt = cdtype(cfg)

    xz = jnp.dot(x.astype(dt), p["in_proj"].astype(dt))
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = constrain(xs, "batch", "seq", "mlp")
    xc = jax.nn.silu(_conv1d_causal(xs, p["conv_w"].astype(dt),
                                    p["conv_b"].astype(dt)))
    delta, bmat, cmat = _ssm_inputs(p, xc, cfg)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, h = mamba_scan(xc, delta, a, bmat, cmat,
                      p["d_skip"].astype(jnp.float32))
    y = y.astype(dt) * jax.nn.silu(z)
    out = jnp.dot(y, p["out_proj"].astype(dt))
    if return_state:
        conv_state = xs[:, -(dc - 1):, :] if s >= dc - 1 else jnp.pad(
            xs, ((0, 0), (dc - 1 - s, 0), (0, 0)))
        return out, (conv_state.astype(dt), jnp.swapaxes(h, 1, 2))
    return out


def _ssm_step(x, delta, a, b, c, d, h):
    """One step of the recurrence for every sequence, in float32: x/delta
    (B, Di), a (N, Di), b/c (B, N), d (Di,), h (B, N, Di) -> (y (B, Di),
    h').  Elementwise products and sums only, so that a TPU keeps every
    term in float32 (a dot would round its operands to bfloat16)."""
    h = (jnp.exp(delta[:, None, :] * a[None]) * h
         + (delta * x)[:, None, :] * b[:, :, None])
    return jnp.sum(h * c[:, :, None], axis=1) + x * d, h


def mamba_decode(p, x: jax.Array, state: Tuple[jax.Array, jax.Array],
                 cfg: ModelConfig):
    """x (B, 1, D), state (conv (B, dc-1, Di), ssm (B, N, Di)) -> (y, state')."""
    conv_state, ssm_state = state
    dt = cdtype(cfg)
    f32 = jnp.float32

    xz = jnp.dot(x.astype(dt), p["in_proj"].astype(dt))
    xs, z = jnp.split(xz, 2, axis=-1)                  # (B, 1, Di)
    window = jnp.concatenate([conv_state, xs], axis=1)  # (B, dc, Di)
    w = p["conv_w"].astype(dt)
    xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, w)
                     + p["conv_b"].astype(dt))          # (B, Di)
    delta, bmat, cmat = _ssm_inputs(p, xc[:, None], cfg)
    a = -jnp.exp(p["a_log"].astype(f32)).T             # (N, Di)
    y, h = _ssm_step(xc.astype(f32), delta[:, 0], a, bmat[:, 0], cmat[:, 0],
                     p["d_skip"].astype(f32), ssm_state.astype(f32))
    y = y[:, None].astype(dt) * jax.nn.silu(z)
    out = jnp.dot(y, p["out_proj"].astype(dt))
    return out, (window[:, 1:], h)


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    di, n, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    return (jnp.zeros((batch, dc - 1, di), dtype),
            jnp.zeros((batch, n, di), jnp.float32))


def mamba_state_struct(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    di, n, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    return (jax.ShapeDtypeStruct((batch, dc - 1, di), dtype),
            jax.ShapeDtypeStruct((batch, n, di), jnp.float32))
