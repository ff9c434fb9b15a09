"""The main path's kernels compiled for a described TPU v5e, without a chip.

Each test compiles one kernel (or one model step) at the widths the served
paths use, for a ``v5e:2x2`` topology that the installed TPU compiler
describes, and checks that the Pallas kernel reached the Mosaic compiler
(``tpu_custom_call`` in the compiled program).  Interpret-mode tests cannot
see what this sees: tiling rules, unsupported vector ops, the VMEM limit.
Nothing runs, so these say nothing about results or speed.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.common import use_interpret

#: device memory of one v5e chip (16 GB of HBM)
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the compiler logs under /tmp unless told otherwise
        mp.setitem(os.environ, "TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the kernels steered onto their TPU path
    and the persistent compilation cache off (a compile for a described
    chip cannot be read back without one)."""
    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        use_interpret.cache_clear()
        jax.clear_caches()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()
    # no trace made for the TPU path may serve a later CPU test
    use_interpret.cache_clear()
    jax.clear_caches()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _mosaic_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("dtype,side", [(jnp.int32, 256), (jnp.int32, 1024),
                                        (jnp.float32, 1024),
                                        (jnp.bfloat16, 1024)])
def test_gemm_compiles(one_chip, dtype, side):
    from repro.kernels.gemm.ops import gemm
    x = jax.ShapeDtypeStruct((side, side), dtype, sharding=one_chip)
    assert _mosaic_kernels(_compile(gemm, x, x)) >= 1


def test_stockham_fft_compiles(one_chip):
    """TinyBio stage 3: 128 windows of 512 samples."""
    from repro.kernels.stockham_fft.stockham_fft import fft_pallas
    x = jax.ShapeDtypeStruct((128, 512), jnp.float32, sharding=one_chip)
    assert _mosaic_kernels(_compile(fft_pallas, x, x)) >= 1


def test_delineate_compiles(one_chip):
    from repro.kernels.delineate.ops import delineate
    x = jax.ShapeDtypeStruct((65_536,), jnp.float32, sharding=one_chip)
    assert _mosaic_kernels(_compile(lambda s: delineate(s, 0), x)) >= 1


def test_fir_compiles(one_chip):
    from repro.kernels.fir.ops import fir
    x = jax.ShapeDtypeStruct((65_536,), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    assert _mosaic_kernels(_compile(fir, x, h)) >= 1


def test_fir_served_batch_compiles(one_chip):
    """The float FIR as the batcher lifts it, vmapped over 16 recordings:
    its Mosaic kernels are named ``fir_pallas`` (the benchmark finds them
    so)."""
    from repro.kernels.fir.ops import fir
    x = jax.ShapeDtypeStruct((16, 65_536), jnp.float32, sharding=one_chip)
    h = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    text = _compile(jax.vmap(fir, in_axes=(0, None)), x, h).as_text()
    kernels = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                         text)
    assert kernels and all(k.startswith("fir_pallas") for k in kernels)


def test_svm_compiles(one_chip):
    """TinyBio stage 4: 128 feature vectors of 36 against 256 SVs."""
    from repro.kernels.svm.ops import svm_decision
    x = jax.ShapeDtypeStruct((128, 36), jnp.float32, sharding=one_chip)
    sv = jax.ShapeDtypeStruct((256, 36), jnp.float32, sharding=one_chip)
    alpha = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=one_chip)
    fn = lambda x, sv, a: svm_decision(x, sv, a, 0.1, 0.5)  # noqa: E731
    assert _mosaic_kernels(_compile(fn, x, sv, alpha)) >= 1


def test_rwkv6_scan_compiles(one_chip):
    """rwkv6-3b widths: 40 heads of 64, 512 steps."""
    from repro.kernels.rwkv6_scan.rwkv6_scan import rwkv6_scan_pallas
    x = jax.ShapeDtypeStruct((1, 40, 512, 64), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((40, 64), jnp.float32, sharding=one_chip)
    assert _mosaic_kernels(_compile(rwkv6_scan_pallas, x, x, x, x, u)) >= 1


def test_mamba_scan_compiles(one_chip):
    """The selective scan of one AI21-Jamba2-3B layer at the longest
    served prompt: 32768 steps over 5120 channels, 16 state entries; its
    Mosaic kernel is named ``mamba_scan_pallas`` (the benchmark finds it
    so)."""
    from repro.kernels.mamba_scan.mamba_scan import mamba_scan_pallas
    x = jax.ShapeDtypeStruct((1, 32768, 5120), jnp.bfloat16,
                             sharding=one_chip)
    delta = jax.ShapeDtypeStruct((1, 32768, 5120), jnp.float32,
                                 sharding=one_chip)
    a = jax.ShapeDtypeStruct((5120, 16), jnp.float32, sharding=one_chip)
    bc = jax.ShapeDtypeStruct((1, 32768, 16), jnp.float32, sharding=one_chip)
    compiled = _compile(mamba_scan_pallas, x, delta, a, bc, bc)
    assert _kernel_names(compiled) == {"mamba_scan_pallas"}


def test_flash_attention_compiles(one_chip):
    """qwen2.5-3b widths: 16 query heads, 2 kv heads of 128, S=512."""
    from repro.kernels.flash_attention.ops import flash_attention
    q = jax.ShapeDtypeStruct((1, 16, 512, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 512, 128), jnp.bfloat16,
                              sharding=one_chip)
    assert _mosaic_kernels(_compile(flash_attention, q, kv, kv)) >= 1


def test_tinybio_served_pipeline_compiles(one_chip):
    """The four TinyBio stages as a Server micro-batch runs them: vmapped
    over a batch of two signals at the paper's workload."""
    from repro.apps.tinybio import TINYBIO_WORKLOAD, tinybio_stages
    from repro.core import EGPU_16T
    stages, _ = tinybio_stages(EGPU_16T)

    def chain(x):
        ins = (x,)
        for st in stages:
            out = st.kernel.executor(*ins, *st.consts, **st.params)
            ins = out if isinstance(out, tuple) else (out,)
        return ins

    x = jax.ShapeDtypeStruct((2, TINYBIO_WORKLOAD["n"]), jnp.float32,
                             sharding=one_chip)
    assert _mosaic_kernels(_compile(jax.vmap(chain), x)) >= 4


def _qwen_params(one_chip):
    from repro.configs import ARCHS
    from repro.models import model_spec
    from repro.models.params import abstract_params
    cfg = ARCHS["qwen2.5-3b"]
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(model_spec(cfg), jnp.dtype(cfg.dtype)))
    return cfg, params


def test_qwen_prefill_step_compiles(one_chip):
    """The whole qwen2.5-3b prefill at published widths, bf16 params, fits
    one chip and attends through the Pallas kernel."""
    from repro.train.serve import make_prefill_step
    cfg, params = _qwen_params(one_chip)
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    compiled = _compile(make_prefill_step(cfg, 1024), params,
                        {"tokens": tokens})
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    assert _mosaic_kernels(compiled) >= 1


def test_qwen_engine_decode_step_compiles(one_chip):
    """The DecodeEngine's 4-slot step graph body at published widths."""
    from repro.core import EGPU_16T, Program
    from repro.models.transformer import cache_struct
    from repro.serve.engine import ENGINE_REGISTRY
    cfg, params = _qwen_params(one_chip)
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.generate", cfg=cfg, num_slots=4, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    cache = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
             for c in jax.tree_util.tree_leaves(
                 cache_struct(cfg, 4, 1024, jnp.bfloat16))]
    io = [jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)] * 2
    donate = tuple(range(2, 2 + len(cache)))
    compiled = jax.jit(kern.executor, donate_argnums=donate).lower(
        *io, *cache, *jax.tree_util.tree_leaves(params)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)


def _deepseek_share(one_chip):
    """The benchmark's DeepSeek-V2 chip share: published widths, 5 layers,
    group 0's 20 experts of the router's 160, 12800 vocabulary rows."""
    import dataclasses

    from repro.configs import ARCHS
    from repro.models import model_spec
    from repro.models.params import abstract_params
    cfg = dataclasses.replace(ARCHS["deepseek-v2-236b"], n_layers=5,
                              experts_held=20, vocab=12800)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(model_spec(cfg), jnp.bfloat16))
    return cfg, params


def _kernel_names(compiled):
    return set(re.findall(
        r"%(\D+?)(?:\.\d+)? = .*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text()))


def test_deepseek_prefill_step_compiles(one_chip):
    """A 12288-token prefill of the DeepSeek-V2 chip share fits one chip;
    MLA attends through the flash kernel at dk 192 / dv 128 and the held
    experts run through the grouped matmul (the DecodeEngine's prefill
    kernel, with its routed experts as an output)."""
    from repro.core import EGPU_16T, Program
    from repro.serve.engine import ENGINE_REGISTRY
    cfg, params = _deepseek_share(one_chip)
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.prefill", cfg=cfg, max_len=12544, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    tokens = jax.ShapeDtypeStruct((1, 12288), jnp.int32, sharding=one_chip)
    compiled = _compile(kern.executor, tokens,
                        *jax.tree_util.tree_leaves(params))
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    assert _kernel_names(compiled) == {"flash_attention_pallas",
                                       "moe_gmm_pallas"}


def test_deepseek_engine_decode_step_compiles(one_chip):
    """The DecodeEngine's 16-slot step over the 12544-position latent
    cache, with the cache donated, fits one chip."""
    from repro.core import EGPU_16T, Program
    from repro.models.transformer import cache_struct
    from repro.serve.engine import ENGINE_REGISTRY
    cfg, params = _deepseek_share(one_chip)
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.generate", cfg=cfg, num_slots=16, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    cache = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
             for c in jax.tree_util.tree_leaves(
                 cache_struct(cfg, 16, 12544, jnp.bfloat16))]
    io = [jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)] * 2
    donate = tuple(range(2, 2 + len(cache)))
    compiled = jax.jit(kern.executor, donate_argnums=donate).lower(
        *io, *cache, *jax.tree_util.tree_leaves(params)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
    assert _kernel_names(compiled) == {"moe_gmm_pallas"}


def _jamba2_3b(one_chip):
    """AI21-Jamba2-3B at its published widths, uncut: 28 layers of 2560,
    attention at index 7 of each period of 14 (20 query heads, one
    key-value head, no rope), Mamba-1 elsewhere (d_inner 5120, d_state
    16, dt_rank 160), dense MLPs of 8192, a tied head over 65536."""
    from repro.models import model_spec
    from repro.models.config import ModelConfig
    from repro.models.params import abstract_params
    cfg = ModelConfig(
        name="jamba2-3b", n_layers=28, d_model=2560, n_heads=20,
        n_kv_heads=1, head_dim=128, d_ff=8192, vocab=65536,
        block_pattern=("mamba",) * 7 + ("attn",) + ("mamba",) * 6,
        mlp_pattern=("dense",) * 14, rotary_pct=0.0, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=160, norm_eps=1e-6,
        tie_embeddings=True, dtype="bfloat16")
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(model_spec(cfg), jnp.bfloat16))
    return cfg, params


def test_jamba_prefill_step_compiles(one_chip):
    """A 32768-token prefill of AI21-Jamba2-3B (the DecodeEngine's prefill
    kernel, cache out to 33280) fits one chip; its Mamba layers scan
    through the Pallas kernel and its attention layers through flash."""
    from repro.core import EGPU_16T, Program
    from repro.serve.engine import ENGINE_REGISTRY
    cfg, params = _jamba2_3b(one_chip)
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.prefill", cfg=cfg, max_len=33280, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    tokens = jax.ShapeDtypeStruct((1, 32768), jnp.int32, sharding=one_chip)
    compiled = _compile(kern.executor, tokens,
                        *jax.tree_util.tree_leaves(params))
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    assert _kernel_names(compiled) == {"flash_attention_pallas",
                                       "mamba_scan_pallas"}


def test_jamba_engine_decode_step_compiles(one_chip):
    """The DecodeEngine's 16-slot step over 33280 positions, its KV rows,
    conv windows and SSM states donated, fits one chip; the decode runs
    no Pallas kernel."""
    from repro.core import EGPU_16T, Program
    from repro.models.transformer import cache_struct
    from repro.serve.engine import ENGINE_REGISTRY
    cfg, params = _jamba2_3b(one_chip)
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.generate", cfg=cfg, num_slots=16, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    cache = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
             for c in jax.tree_util.tree_leaves(
                 cache_struct(cfg, 16, 33280, jnp.bfloat16))]
    io = [jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)] * 2
    donate = tuple(range(2, 2 + len(cache)))
    compiled = jax.jit(kern.executor, donate_argnums=donate).lower(
        *io, *cache, *jax.tree_util.tree_leaves(params)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
    assert _kernel_names(compiled) == set()


@pytest.mark.parametrize("rows,tm", [(79104, 256), (400, 16)],
                         ids=["prefill", "decode"])
def test_moe_gmm_compiles(one_chip, rows, tm):
    """The grouped matmuls of one DeepSeek-V2 layer at the row counts of a
    12288-token prefill and of a 16-slot decode step (20 held experts):
    gate/up (5120 -> 1536) and down (1536 -> 5120)."""
    from repro.kernels.moe_gmm.ops import moe_gmm

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    gs = spec(20, dtype=jnp.int32)
    for k, n in ((5120, 1536), (1536, 5120)):
        fn = lambda x, w, g: moe_gmm(x, w, g, tm=tm)  # noqa: E731
        compiled = _compile(fn, spec(rows, k), spec(20, k, n), gs)
        assert _kernel_names(compiled) == {"moe_gmm_pallas"}


def _whole_leaf_moves(text: str, leaves) -> list:
    """Each copy, reshape or transpose in compiled ``text`` whose result
    holds as many elements as one of ``leaves``: a whole cache leaf moved
    rather than updated in place."""
    sizes = {math.prod(s.shape) for s in leaves}
    moves = []
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* (copy|reshape|transpose)\(",
                         text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if math.prod(dims) in sizes:
            moves.append(m.group(0))
    return moves


@pytest.mark.parametrize("case", ["qwen-chat", "deepseek-longdoc",
                                  "jamba-longctx"])
def test_engine_decode_step_writes_cache_in_place(one_chip, case):
    """The engine's decode step at the chat cell's widths (qwen2.5-3b, 32
    slots of 2048), at the longdoc cell's chip share (DeepSeek-V2, 16
    slots of 12544) and at the longctx cell's (AI21-Jamba2-3B, 16 slots of
    33280: KV rows, conv windows and SSM states), its cache donated, keeps
    no second cache: its temporaries stay under a tenth of the slot
    state's bytes, and no copy, reshape or transpose moves a whole cache
    leaf.  Each step writes only every slot's new row (a Mamba layer its
    own state) in place."""
    from repro.core import EGPU_16T, Program
    from repro.models.transformer import cache_struct
    from repro.serve.engine import ENGINE_REGISTRY
    if case == "qwen-chat":
        (cfg, params), slots, max_len = _qwen_params(one_chip), 32, 2048
    elif case == "deepseek-longdoc":
        (cfg, params), slots, max_len = _deepseek_share(one_chip), 16, 12544
    else:
        (cfg, params), slots, max_len = _jamba2_3b(one_chip), 16, 33280
    kern = Program.build(EGPU_16T, registry=ENGINE_REGISTRY).create_kernel(
        "engine.generate", cfg=cfg, num_slots=slots, cache_dtype="bfloat16")
    kern.executor._params_def = jax.tree_util.tree_structure(params)
    cache = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
             for c in jax.tree_util.tree_leaves(
                 cache_struct(cfg, slots, max_len, jnp.bfloat16))]
    io = [jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)] * 2
    donate = tuple(range(2, 2 + len(cache)))
    compiled = jax.jit(kern.executor, donate_argnums=donate).lower(
        *io, *cache, *jax.tree_util.tree_leaves(params)).compile()
    cache_bytes = sum(math.prod(c.shape) * c.dtype.itemsize for c in cache)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * cache_bytes
    assert _whole_leaf_moves(compiled.as_text(), cache) == []
