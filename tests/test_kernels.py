"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.

Every Pallas kernel targets TPU (pl.pallas_call + BlockSpec) and validates
here in interpret mode; the XLA fallbacks are swept too via impl flags.
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gemm.ops import gemm
from repro.kernels.gemm.ref import gemm_ref
from repro.kernels.fir.ops import fir
from repro.kernels.fir.ref import fir_ref
from repro.kernels.stockham_fft.ops import fft, power_spectrum
from repro.kernels.stockham_fft.ref import stockham_fft_ref
from repro.kernels.delineate.ops import delineate
from repro.kernels.delineate.ref import delineate_ref
from repro.kernels.svm.ops import svm_decision
from repro.kernels.svm.ref import svm_decision_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.decode_attention.ops import (combine_partials,
                                                decode_attention,
                                                decode_attention_partial_ref,
                                                decode_attention_ref)
from repro.kernels.rwkv6_scan.ops import rwkv6_scan, rwkv6_scan_ref
from repro.kernels.mamba_scan.ops import mamba_scan, mamba_scan_ref
from repro.kernels.moe_gmm.ops import moe_gmm, moe_gmm_ref, tile_plan

RNG = np.random.default_rng(0)


def rand(*shape, dtype=np.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape).astype(dtype) * scale)


# ---------------------------------------------------------------------------
# GeMM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (100, 70, 50), (128, 128, 128),
                                   (257, 129, 65), (512, 256, 384)])
def test_gemm_shapes(m, k, n):
    a, b = rand(m, k), rand(k, n)
    np.testing.assert_allclose(gemm(a, b), gemm_ref(a, b),
                               rtol=2e-4, atol=2e-4)


def test_gemm_int32_fixed_point():
    a = jnp.asarray(RNG.integers(-100, 100, (64, 32)), jnp.int32)
    b = jnp.asarray(RNG.integers(-100, 100, (32, 48)), jnp.int32)
    np.testing.assert_array_equal(gemm(a, b), gemm_ref(a, b))


# ---------------------------------------------------------------------------
# FIR
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,taps", [(64, 8), (1000, 31), (4096, 128)])
def test_fir(n, taps):
    x, h = rand(n), rand(taps)
    np.testing.assert_allclose(fir(x, h), fir_ref(x, h), rtol=2e-4, atol=2e-4)


def test_fir_matches_numpy_convolve():
    x, h = rand(512), rand(17)
    ref = np.convolve(np.asarray(x), np.asarray(h))[:512]
    np.testing.assert_allclose(fir(x, h), ref, rtol=1e-4, atol=1e-4)


def band_case(shape, taps):
    """A signal and taps with unit L1 norm, as the served filter has
    (|y| <= max |x|), from a generator of their own."""
    rng = np.random.default_rng([taps, *shape])
    h = rng.standard_normal(taps).astype(np.float32)
    return (jnp.asarray(rng.standard_normal(shape).astype(np.float32)),
            jnp.asarray(h / np.abs(h).sum()))


@pytest.mark.parametrize("n,taps,block", [
    (65_536, 128, 512),     # the served recording: one tile, K = 2
    (4096, 31, 512),        # taps that do not divide 128
    (4096, 200, 512),       # K = 3
    (1000, 128, 512),       # n not a multiple of 128
    (8192, 200, 16),        # four tiles of 16 rows: the halo carries
])
def test_fir_band(n, taps, block):
    """The float path's banded Toeplitz product against the oracle."""
    x, h = band_case((n,), taps)
    np.testing.assert_allclose(fir(x, h, block), fir_ref(x, h),
                               rtol=1e-5, atol=1e-5)


def test_fir_band_vmapped_batch():
    """A batch of 16 served recordings, lifted as the batcher lifts the
    stage, equals the oracle row by row."""
    x, h = band_case((16, 65_536), 128)
    y = jax.vmap(fir, in_axes=(0, None))(x, h)
    for row in range(16):
        np.testing.assert_allclose(y[row], fir_ref(x[row], h),
                                   rtol=1e-5, atol=1e-5)


def test_fir_int_fixed_point():
    x = jnp.asarray(RNG.integers(-2000, 2000, 256), jnp.int32)
    h = jnp.asarray(RNG.integers(-300, 300, 16), jnp.int32)
    np.testing.assert_array_equal(fir(x, h), fir_ref(x, h))


# ---------------------------------------------------------------------------
# Stockham FFT
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [64, 256, 1024, 4096])
def test_fft_vs_numpy(n):
    x = rand(n)
    re, im = fft(x)
    ref = np.fft.fft(np.asarray(x))
    np.testing.assert_allclose(re, ref.real, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(im, ref.imag, rtol=1e-3, atol=1e-3)


def test_fft_matches_ref_and_batched():
    x = rand(8, 512)
    re, im = fft(x, jnp.zeros_like(x))
    rr, ri = stockham_fft_ref(x[0], jnp.zeros(512))
    np.testing.assert_allclose(re[0], rr, rtol=1e-3, atol=1e-3)
    ps = power_spectrum(x[0])
    np.testing.assert_allclose(
        ps, np.abs(np.fft.fft(np.asarray(x[0]))) ** 2, rtol=1e-2, atol=1e-2)


# ---------------------------------------------------------------------------
# Delineation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [50, 512, 4097])
def test_delineate(n):
    x = rand(n)
    np.testing.assert_array_equal(delineate(x), delineate_ref(x))


def test_delineate_finds_known_extrema():
    t = np.linspace(0, 6 * np.pi, 600).astype(np.float32)
    x = jnp.asarray(np.sin(t))
    flags = np.asarray(delineate(x))
    peaks = np.where(flags > 0)[0]
    troughs = np.where(flags < 0)[0]
    assert len(peaks) == 3 and len(troughs) == 3
    # peaks of sin at pi/2 + 2k pi
    np.testing.assert_allclose(t[peaks], [np.pi / 2, np.pi * 2.5, np.pi * 4.5],
                               atol=0.05)


# ---------------------------------------------------------------------------
# SVM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("q,m,d,gamma", [(1, 16, 8, 0.5), (5, 40, 12, 0.3),
                                         (16, 256, 32, None)])
def test_svm(q, m, d, gamma):
    x, sv = rand(q, d), rand(m, d)
    alpha = rand(m, scale=0.1)
    out = svm_decision(x, sv, alpha, 0.25, gamma)
    ref = svm_decision_ref(x, sv, alpha, 0.25, gamma)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kvh,s,d", [(1, 4, 4, 128, 32), (2, 4, 2, 256, 64),
                                         (1, 8, 1, 512, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_xla(b, h, kvh, s, d, causal):
    q, k, v = rand(b, h, s, d), rand(b, kvh, s, d), rand(b, kvh, s, d)
    out = flash_attention(q, k, v, causal=causal, impl="xla")
    ref = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_pallas_interpret():
    q, k, v = rand(1, 4, 256, 64), rand(1, 2, 256, 64), rand(1, 2, 256, 64)
    out = flash_attention(q, k, v, causal=True, impl="pallas", bq=128, bk=128)
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_q_offset_decode_suffix():
    """q as a suffix of the sequence (chunked prefill)."""
    q, k, v = rand(1, 4, 64, 32), rand(1, 4, 256, 32), rand(1, 4, 256, 32)
    out = flash_attention(q, k, v, causal=True, q_offset=192, impl="xla")
    ref = mha_ref(q, k, v, causal=True, q_offset=192)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_attention_mla_asymmetric_dv():
    """MLA uses Dk=192 vs Dv=128."""
    q, k, v = rand(1, 4, 128, 96), rand(1, 4, 128, 96), rand(1, 4, 128, 64)
    out = flash_attention(q, k, v, causal=True, impl="xla")
    ref = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Decode attention (flash-decoding)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kvh,t,d", [(2, 4, 2, 512, 64), (1, 8, 8, 128, 32)])
def test_decode_attention(b, h, kvh, t, d):
    q = rand(b, h, d)
    k, v = rand(b, kvh, t, d), rand(b, kvh, t, d)
    out = decode_attention(q, k, v)
    ref = decode_attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


def test_flash_decoding_combine_identity():
    """Seq-sharded partial-softmax combine == full softmax (exact)."""
    q = rand(2, 4, 32)
    k, v = rand(2, 4, 256, 32), rand(2, 4, 256, 32)
    full = decode_attention_ref(q, k, v)
    parts = [decode_attention_partial_ref(q, k[:, :, i*64:(i+1)*64],
                                          v[:, :, i*64:(i+1)*64])
             for i in range(4)]
    merged, _, _ = combine_partials(parts)
    np.testing.assert_allclose(merged.astype(full.dtype), full,
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RWKV-6 scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,t,d", [(1, 2, 16, 8), (2, 4, 64, 16)])
def test_rwkv6_scan(b, h, t, d):
    r, k, v = rand(b, h, t, d, scale=0.3), rand(b, h, t, d, scale=0.3), \
        rand(b, h, t, d, scale=0.3)
    w = jnp.asarray(RNG.random((b, h, t, d)).astype(np.float32) * 0.5 + 0.3)
    u = rand(h, d, scale=0.3)
    y, s = rwkv6_scan(r, k, v, w, u, impl="xla")
    yr, sr = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(y, yr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(s, sr, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("b,h,t,d", [(1, 2, 64, 16), (2, 3, 40, 8)])
def test_rwkv6_scan_pallas_interpret(b, h, t, d):
    """The kernel the chip runs (chunked, state in VMEM scratch), in
    interpret mode, against the sequential oracle."""
    r, k, v = rand(b, h, t, d, scale=0.3), rand(b, h, t, d, scale=0.3), \
        rand(b, h, t, d, scale=0.3)
    w = jnp.asarray(RNG.random((b, h, t, d)).astype(np.float32) * 0.5 + 0.3)
    u = rand(h, d, scale=0.3)
    y, s = rwkv6_scan(r, k, v, w, u, impl="pallas")
    yr, sr = rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(y, yr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(s, sr, rtol=2e-3, atol=2e-3)


def test_rwkv6_chunked_equals_sequential():
    """State chaining across chunks is exact."""
    b, h, t, d = 1, 2, 64, 16
    r, k, v = rand(b, h, t, d, scale=0.3), rand(b, h, t, d, scale=0.3), \
        rand(b, h, t, d, scale=0.3)
    w = jnp.asarray(RNG.random((b, h, t, d)).astype(np.float32) * 0.5 + 0.3)
    u = rand(h, d, scale=0.3)
    y_full, s_full = rwkv6_scan_ref(r, k, v, w, u)
    y1, s1 = rwkv6_scan_ref(r[:, :, :32], k[:, :, :32], v[:, :, :32],
                            w[:, :, :32], u)
    y2, s2 = rwkv6_scan_ref(r[:, :, 32:], k[:, :, 32:], v[:, :, 32:],
                            w[:, :, 32:], u, state0=s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 2), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s2, s_full, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Mamba scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,dm,n", [(1, 32, 16, 8), (2, 64, 32, 16)])
def test_mamba_scan(b, t, dm, n):
    x, delta = rand(b, t, dm, scale=0.5), \
        jnp.abs(rand(b, t, dm, scale=0.3)) + 0.1
    a = -jnp.abs(rand(dm, n)) - 0.1
    bb, cc = rand(b, t, n, scale=0.5), rand(b, t, n, scale=0.5)
    d = rand(dm, scale=0.5)
    y, s = mamba_scan(x, delta, a, bb, cc, d, impl="xla")
    yr, sr = mamba_scan_ref(x, delta, a, bb, cc, d)
    np.testing.assert_allclose(y, yr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(s, sr, rtol=2e-3, atol=2e-3)


def test_mamba_chunked_equals_sequential():
    b, t, dm, n = 1, 64, 16, 8
    x, delta = rand(b, t, dm, scale=0.5), \
        jnp.abs(rand(b, t, dm, scale=0.3)) + 0.1
    a = -jnp.abs(rand(dm, n)) - 0.1
    bb, cc = rand(b, t, n, scale=0.5), rand(b, t, n, scale=0.5)
    d = rand(dm, scale=0.5)
    y_full, s_full = mamba_scan_ref(x, delta, a, bb, cc, d)
    y1, s1 = mamba_scan_ref(x[:, :32], delta[:, :32], a, bb[:, :32],
                            cc[:, :32], d)
    y2, s2 = mamba_scan_ref(x[:, 32:], delta[:, 32:], a, bb[:, 32:],
                            cc[:, 32:], d, state0=s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s2, s_full, rtol=1e-4, atol=1e-4)


# -- grouped matmul over expert-sorted rows ----------------------------------
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("sizes,tm,k,n", [
    ((32, 0, 16, 48, 0), 16, 256, 384),     # an expert with no rows
    ((0, 0, 0, 0, 0), 16, 128, 128),        # no row routed to this chip
    ((256, 512, 0), 256, 384, 256),         # prefill-sized tiles
    ((16,), 16, 96, 200),                   # widths off the 128 lanes
])
def test_moe_gmm(impl, sizes, tm, k, n):
    """Pallas (interpret mode) and ragged_dot against the tile oracle on
    every routed row; f32 operands, so only the summation order differs."""
    gs = jnp.asarray(sizes, jnp.int32)
    m = sum(sizes) + 2 * tm                 # unused tiles after the groups
    x, w = rand(m, k), rand(len(sizes), k, n, scale=k ** -0.5)
    tile_expert, n_active = tile_plan(gs, m, tm)
    want = moe_gmm_ref(x, w, tile_expert, n_active, tm)
    got = moe_gmm(x, w, gs, tm=tm, impl=impl)
    r = sum(sizes)
    np.testing.assert_allclose(got[:r], want[:r], rtol=1e-5, atol=1e-5)
    assert int(n_active[0]) * tm == r


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_moe_gmm_reads_one_layer_of_a_stack(impl):
    """Weights stacked over layers (L, E, K, N) with a layer index give what
    that layer's (E, K, N) gives."""
    gs = jnp.asarray([16, 32, 0], jnp.int32)
    x, w = rand(64, 128), rand(3, 3, 128, 256, scale=128 ** -0.5)
    want = moe_gmm(x, w[2], gs, tm=16, impl=impl)
    got = moe_gmm(x, w, gs, jnp.int32(2), tm=16, impl=impl)
    np.testing.assert_array_equal(got[:48], want[:48])


def test_moe_gmm_tile_plan_skips_past_the_last_group():
    te, na = tile_plan(jnp.asarray([32, 0, 16, 48, 0]), 160, 16)
    assert int(na[0]) == 6
    # tiles past the active ones repeat the last active tile's expert, so
    # the kernel's index maps fetch nothing new for them
    assert list(np.asarray(te)) == [0, 0, 2, 3, 3, 3, 3, 3, 3, 3]


# -- device names ------------------------------------------------------------
KERNELS_DIR = (pathlib.Path(__file__).resolve().parents[1]
               / "src" / "repro" / "kernels")


def _pallas_calls(path):
    """(enclosing function, its pallas_call node) of every call in ``path``."""
    out = []
    for fn in ast.walk(ast.parse(path.read_text())):
        if isinstance(fn, ast.FunctionDef):
            out += [(fn.name, c) for c in ast.walk(fn)
                    if isinstance(c, ast.Call)
                    and getattr(c.func, "attr",
                                getattr(c.func, "id", None)) == "pallas_call"]
    return out


PALLAS_FILES = sorted(p for p in KERNELS_DIR.rglob("*.py") if _pallas_calls(p))


def test_every_kernel_package_is_guarded():
    assert {p.parent.name for p in PALLAS_FILES} == {
        "decode_attention", "delineate", "fir", "flash_attention", "gemm",
        "mamba_scan", "moe_gmm", "rwkv6_scan", "stockham_fft", "svm"}


@pytest.mark.parametrize("path", PALLAS_FILES,
                         ids=lambda p: p.relative_to(KERNELS_DIR).as_posix())
def test_pallas_call_is_named_after_its_wrapper(path):
    """The device trace names a kernel's operation after its wrapper, and
    the benchmark's kernel patterns match that name: each ``pallas_call``
    states it, so the name survives a wrapper that is inlined."""
    for wrapper, call in _pallas_calls(path):
        names = [k.value for k in call.keywords if k.arg == "name"]
        assert names, f"{path.name}: pallas_call in {wrapper} has no name="
        assert isinstance(names[0], ast.Constant), path.name
        assert names[0].value == wrapper, (path.name, names[0].value)
