"""Plain reference of the TinyBio pipeline (e-GPU paper, arXiv:2505.08421).

recording -> causal FIR band-pass -> delineation (peak/trough flags) ->
Stockham-FFT band powers and time statistics per window -> RBF-SVM
decision per window.  The mathematics follows the paper's stages as the
program's pure ``ref.py`` oracles state them; this file imports nothing of
the program and rebuilds the filter taps and the SVM's support vectors
from the seed the same published way.

``dtype=jnp.bfloat16`` computes every stage in bfloat16, the precision
below the float32 the configuration states: the control that the
correctness check must fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def constants(cfg: dict, seed: int) -> tuple:
    """(taps h, support vectors, dual coefficients) for ``seed``."""
    taps = cfg["taps"]
    h = np.hamming(taps) * np.sinc(np.linspace(-4, 4, taps))
    h = np.asarray(h / np.abs(h).sum(), np.float32)
    rng = np.random.default_rng(seed + 1)
    sv = np.asarray(rng.standard_normal((cfg["n_sv"], cfg["n_features"])),
                    np.float32)
    alpha = np.asarray(rng.standard_normal(cfg["n_sv"]) / cfg["n_sv"],
                       np.float32)
    return h, sv, alpha


def fir(x, h):
    """y[n] = sum_t h[t] x[n - t], zero history."""
    taps, n = h.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1,), x.dtype), x])
    idx = jnp.arange(n)[:, None] + jnp.arange(taps)[None, :]
    return xp[idx] @ h[::-1]


def delineate(x):
    """+1 at a strict rise followed by a non-strict fall above 0, -1 at the
    mirror image below 0, else 0; the end samples are never flagged."""
    prev = jnp.concatenate([x[:1], x[:-1]])
    nxt = jnp.concatenate([x[1:], x[-1:]])
    idx = jnp.arange(x.shape[0])
    inner = (idx > 0) & (idx < x.shape[0] - 1)
    peak = (x > prev) & (x >= nxt) & (x > 0) & inner
    trough = (x < prev) & (x <= nxt) & (x < 0) & inner
    return peak.astype(jnp.int32) - trough.astype(jnp.int32)


def fft(re, im):
    """Radix-2 Stockham FFT of power-of-two length, real/imag planes."""
    n = re.shape[0]
    re, im = re.reshape(n, 1), im.reshape(n, 1)
    while re.shape[0] > 1:
        r, l = re.shape[0] // 2, re.shape[1]
        ang = -math.pi * jnp.arange(l, dtype=jnp.float32) / l
        wr, wi = jnp.cos(ang).astype(re.dtype), jnp.sin(ang).astype(re.dtype)
        ar, ai, br, bi = re[:r], im[:r], re[r:], im[r:]
        tr, ti = wr * br - wi * bi, wr * bi + wi * br
        re = jnp.concatenate([ar + tr, ar - tr], axis=1)
        im = jnp.concatenate([ai + ti, ai - ti], axis=1)
    return re.reshape(n), im.reshape(n)


def features(x, flags, cfg):
    win, nw, nf = cfg["win"], cfg["n_windows"], cfg["n_features"]
    w = x[: win * nw].reshape(nw, win)
    re, im = jax.vmap(fft)(w, jnp.zeros_like(w))
    spec = re * re + im * im
    bands = spec[:, : win // 2].reshape(nw, nf - 4, -1).mean(-1)
    f = flags[: win * nw].reshape(nw, win)
    feats = jnp.concatenate([
        bands, w.mean(1, keepdims=True),
        jnp.sqrt((w * w).mean(1, keepdims=True)),
        (f > 0).sum(1, keepdims=True).astype(x.dtype),
        (f < 0).sum(1, keepdims=True).astype(x.dtype)], axis=1)
    return feats / (jnp.abs(feats).max(axis=0, keepdims=True) + 1e-6)


def svm(q, sv, alpha, b, gamma):
    dots = q @ sv.T
    d2 = (jnp.sum(q * q, 1, keepdims=True) + jnp.sum(sv * sv, 1)[None, :]
          - 2.0 * dots)
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0)) @ alpha + b


@functools.partial(jax.jit, static_argnames=("cfgkey", "dtype"))
def _pipeline(x, h, sv, alpha, *, cfgkey, dtype):
    cfg = dict(cfgkey)
    x, h, sv, alpha = (a.astype(dtype) for a in (x, h, sv, alpha))
    y = fir(x, h)
    flags = delineate(y)
    q = features(y, flags, cfg)
    return svm(q, sv, alpha, jnp.asarray(cfg["svm_bias"], dtype),
               cfg["gamma"]).astype(jnp.float32)


def pipeline(x: np.ndarray, cfg: dict, consts: tuple,
             dtype=jnp.float32) -> np.ndarray:
    """Decision values (n_windows,) for one recording."""
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float))))
    with jax.default_matmul_precision("highest"):
        out = _pipeline(jnp.asarray(x), *(jnp.asarray(c) for c in consts),
                        cfgkey=key, dtype=jnp.dtype(dtype))
    return np.asarray(out)
