"""TinyBio — the paper's 4-stage biosignal pipeline (MBio-Tracker, Fig 4).

    raw signal → FIR band-pass → delineation (peaks/troughs)
               → Stockham-FFT spectral features (+ time features)
               → SVM cognitive-workload decision

Workload (fixed, documented in EXPERIMENTS.md §Paper-validation): a 65536-
sample int16 recording (≈ 34 min of respiration @ 32 Hz), 128-tap FIR,
spectral features over 128 windows of 512 samples, SVM over 256 support
vectors x 32 features.  With this workload the analytic machine model
reproduces the paper's Fig-4 bands within ±15 % on every stage
(tests/test_paper_validation.py pins them).

Every stage runs functionally (Pallas kernels on TPU, interpret mode on CPU)
AND is costed by the machine model — the APU report carries both.
``tinybio_stages(use_pallas=False)`` builds the same pipeline from the
kernels' ``ref.py`` oracles, the reference the Pallas pipeline is checked
against.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (APU, EGPUConfig, EGPU_16T, Kernel, Program, Stage,
                    kernel_family)
from ..kernels.stockham_fft import ops as fft_ops
from ..kernels.stockham_fft.ref import counts as fft_counts
from ..kernels.stockham_fft.ref import stockham_fft_ref

TINYBIO_WORKLOAD = dict(n=65_536, taps=128, win=512, n_windows=128,
                        n_sv=256, n_features=36)   # 32 bands + 4 stats


def synth_signal(n: int, seed: int = 0) -> np.ndarray:
    """Synthetic respiration-like signal: slow oscillation + drift + noise."""
    rng = np.random.default_rng(seed)
    t = np.arange(n) / 32.0
    breath = np.sin(2 * np.pi * 0.25 * t) + 0.3 * np.sin(2 * np.pi * 0.08 * t)
    sig = breath + 0.1 * rng.standard_normal(n)
    return np.asarray(sig, np.float32)


def _power_spectrum_ref(w: jax.Array) -> jax.Array:
    """|FFT|^2 of each row via the pure-jnp Stockham oracle."""
    re, im = jax.vmap(stockham_fft_ref)(w, jnp.zeros_like(w))
    return re * re + im * im


def _feature_kernel(win: int, n_windows: int, use_pallas: bool = True):
    """Stage 3: windowed power-spectrum features + time-domain stats."""
    spectrum = fft_ops.power_spectrum if use_pallas else _power_spectrum_ref

    def features(x: jax.Array, flags: jax.Array) -> jax.Array:
        w = x[: win * n_windows].reshape(n_windows, win)
        spec = spectrum(w)                                  # (NW, win)
        nf = TINYBIO_WORKLOAD["n_features"]
        bands = spec[:, :win // 2].reshape(n_windows, nf - 4, -1).mean(-1)
        mean = w.mean(axis=1, keepdims=True)
        rms = jnp.sqrt((w * w).mean(axis=1, keepdims=True))
        peaks = (flags[: win * n_windows].reshape(n_windows, win) > 0
                 ).sum(axis=1, keepdims=True).astype(jnp.float32)
        troughs = (flags[: win * n_windows].reshape(n_windows, win) < 0
                   ).sum(axis=1, keepdims=True).astype(jnp.float32)
        feats = jnp.concatenate([bands, mean, rms, peaks, troughs], axis=1)
        # normalize for the RBF kernel
        return feats / (jnp.abs(feats).max(axis=0, keepdims=True) + 1e-6)
    return features


# App-level Tiny-OpenCL registration (host API v2): TinyBio's two composite
# stages join the same kernel registry the built-in families live in, so
# they get the registry's memoization — repeated ``tinybio_stages`` calls
# reuse the exact kernel objects, which keeps executor jit caches warm and
# serve GraphCache keys stable — and show how applications extend the
# program without touching repro.kernels.

@kernel_family("tinybio.delineate_keep")
def _build_delineate_keep(config: EGPUConfig = EGPU_16T, *,
                          use_pallas: bool = True) -> Kernel:
    """Delineation that also passes the filtered signal through:
    x -> (x, flags)."""
    del_k = Program.build(config).create_kernel("delineate",
                                                use_pallas=use_pallas)
    return Kernel("delineate_keep",
                  executor=lambda x: (x, del_k.executor(x)),
                  counts=del_k.counts)


@kernel_family("tinybio.fft_features")
def _build_fft_features(config: EGPUConfig = EGPU_16T, *, win: int = 512,
                        n_windows: int = 128,
                        use_pallas: bool = True) -> Kernel:
    """Stage-3 spectral+time features at a fixed windowing."""
    return Kernel(name="fft_features",
                  executor=_feature_kernel(win, n_windows, use_pallas),
                  counts=lambda **kw: fft_counts(n=win).scaled(n_windows))


def tinybio_stages(config: EGPUConfig = EGPU_16T, seed: int = 0,
                   use_pallas: bool = True):
    """(stages, inputs) for :meth:`repro.core.APU.offload`; with
    ``use_pallas=False`` every stage runs its ``ref.py`` oracle."""
    wl = TINYBIO_WORKLOAD
    n, taps, win, nw = wl["n"], wl["taps"], wl["win"], wl["n_windows"]
    rng = np.random.default_rng(seed + 1)
    h = np.asarray(np.hamming(taps) * np.sinc(np.linspace(-4, 4, taps)),
                   np.float32)
    h /= np.abs(h).sum()
    sv = np.asarray(rng.standard_normal((wl["n_sv"], wl["n_features"])),
                    np.float32)
    alpha = np.asarray(rng.standard_normal(wl["n_sv"]) / wl["n_sv"],
                       np.float32)

    # Host API v2: kernels come from the Tiny-OpenCL program registry —
    # memoized per (family, config, variant), so repeated stage builds (a
    # serving loop re-wiring the pipeline per offload) reuse the SAME
    # kernel objects, keep their compiled executors warm, and give the
    # serve GraphCache a stable registry identity to key on.
    program = Program.build(config)
    stages = [
        Stage(program.create_kernel("fir", use_pallas=use_pallas),
              consts=(jnp.asarray(h),),
              counts_params={"n": n, "taps": taps, "itemsize": 2}),
        # delineate consumes the filtered signal; passes (signal, flags) on
        Stage(program.create_kernel("tinybio.delineate_keep",
                                    use_pallas=use_pallas),
              counts_params={"n": n}),
        Stage(program.create_kernel("tinybio.fft_features", win=win,
                                    n_windows=nw, use_pallas=use_pallas),
              counts_params={}),
        Stage(program.create_kernel("svm", use_pallas=use_pallas),
              consts=(jnp.asarray(sv), jnp.asarray(alpha),
                      jnp.float32(0.1)),
              params={"gamma": 0.5},
              counts_params={"q": nw, "m": wl["n_sv"],
                             "d": wl["n_features"]}),
    ]
    inputs = (jnp.asarray(synth_signal(n, seed)),)
    return stages, inputs


def run_tinybio(config: EGPUConfig = EGPU_16T, seed: int = 0,
                mode: str = "graph") -> Tuple[jax.Array, "object"]:
    """Run the full pipeline on an APU; returns (decisions, report).

    ``mode="graph"`` (default) captures all four stages into one TinyCL
    :class:`~repro.core.runtime.CommandGraph` and dispatches them as a
    single fused XLA computation (per-stage machine-model accounting is
    taken from the captured schedule); ``mode="eager"`` dispatches each
    stage as its own kernel launch.
    """
    apu = APU(config)
    outs, report = apu.offload(*tinybio_stages(config, seed), mode=mode)
    return outs[0].data, report
