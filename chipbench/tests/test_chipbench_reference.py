"""The plain references agree with the program at a reduced size on the
CPU: the qwen2 forward with the program's prefill and cached decode in
float32, the TinyBio maths with the program's pure-jnp stage chain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtree
from chipbench.families import qwen2, tinybio
from chipbench.reference import qwen2 as ref_qwen2
from chipbench.reference import tinybio as ref_tinybio


@pytest.fixture(scope="module")
def lm():
    import json

    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "qwen2.5-3b.json").read_text())
    cfg.update(benchtree.TINY_LM, torch_dtype="float32", name="tiny")
    weights = qwen2.make_weights(cfg, 9)
    mc = qwen2.program_config(cfg)
    return cfg, weights, mc, qwen2.to_program(weights, mc)


def test_qwen2_prefill_and_cached_decode_match_the_reference(lm):
    from repro.models.transformer import decode_step, prefill

    cfg, weights, mc, params = lm
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 12,
                                             dtype=np.int32)
    want = np.asarray(ref_qwen2.logits(weights, cfg, toks, 16))
    with jax.default_matmul_precision("highest"):
        got, cache = prefill(params, {"tokens": jnp.asarray(toks[None, :8])},
                             mc, 16, cache_dtype=jnp.float32)
        rows = [np.asarray(got[0, : cfg["vocab_size"]])]
        for i in range(8, 12):
            logits, cache = decode_step(params, cache,
                                        jnp.asarray(toks[i: i + 1]),
                                        jnp.int32(i), mc)
            rows.append(np.asarray(logits[0, : cfg["vocab_size"]]))
    np.testing.assert_allclose(np.stack(rows), want[7:12], rtol=1e-4,
                               atol=1e-4)


def test_qwen2_control_is_coarser(lm):
    cfg, weights, _, _ = lm
    toks = np.arange(10, dtype=np.int32)
    want = ref_qwen2.logits(weights, cfg, toks, 16)
    low = ref_qwen2.logits(weights, cfg, toks, 16, control=True)
    err = float(jnp.max(jnp.abs(low - want)))
    assert 1e-3 < err < 0.5 * float(jnp.max(jnp.abs(want)))


def test_e4m3_rounding():
    x = jnp.asarray([[448.0, 1.0, 1.06, -3.3, 0.0]])
    y = np.asarray(ref_qwen2.round_e4m3(x, -1))
    np.testing.assert_array_equal(y, [[448.0, 1.0, 1.0, -3.25, 0.0]])


def test_tinybio_reference_matches_the_program_chain():
    from repro.apps.tinybio import tinybio_stages
    from repro.core import EGPU_16T

    cfg = benchtree.json.loads((benchtree.ROOT / "chipbench" / "configs"
                                / "tinybio.json").read_text())
    x = tinybio.recordings(cfg, 3, 1)[0]
    stages, _ = tinybio_stages(EGPU_16T, 3, use_pallas=False)
    ins = (jnp.asarray(x),)
    with jax.default_matmul_precision("highest"):
        for st in stages:
            out = st.kernel.executor(*ins, *st.consts, **st.params)
            ins = out if isinstance(out, tuple) else (out,)
    want = ref_tinybio.pipeline(x, cfg, ref_tinybio.constants(cfg, 3))
    np.testing.assert_allclose(np.asarray(ins[0]), want, rtol=1e-5,
                               atol=1e-6)
    low = ref_tinybio.pipeline(x, cfg, ref_tinybio.constants(cfg, 3),
                               dtype="bfloat16")
    assert np.max(np.abs(low - want)) > 1e-4 * np.max(np.abs(want))
