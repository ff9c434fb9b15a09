"""The Jamba hybrid against the plain reference (``repro.models.
reference_jamba``) on seeded random weights: the published structure of
AI21-Jamba2-3B (a period of 14 layers, attention at index 7, Mamba-1 with
its dt/B/C norms everywhere else, MQA without positions, dense SwiGLU
MLPs, a tied head) at small widths.

The program runs in float32 here, so each tolerance covers only the order
in which float32 sums are taken: the XLA scan's chunked associative form
against the reference's one step after another, flash attention's online
softmax, the decode step's batched matmuls.  That is 2e-4 on logits of
size about 1 (``TOL``).  Rounding the SSM state or the scan's inputs to
bfloat16 moves the logits by far more, and the control test shows it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mamba_scan.ops import mamba_scan, mamba_scan_ref
from repro.models import init_params, model_spec
from repro.models import mamba as mamba_mod
from repro.models import reference_jamba as ref
from repro.models.config import ModelConfig
from repro.models.layers import apply_rotary
from repro.models.mamba import mamba_full
from repro.models.transformer import decode_step, prefill
from repro.obs import MetricsRegistry
from repro.serve import DecodeEngine, Server

#: logits agree to this (float32 program against the float32 reference)
TOL = 2e-4

CFG = ModelConfig(
    name="jamba-small", n_layers=28, d_model=128, n_heads=4, n_kv_heads=1,
    head_dim=32, d_ff=256, vocab=512,
    block_pattern=("mamba",) * 7 + ("attn",) + ("mamba",) * 6,
    mlp_pattern=("dense",) * 14, rotary_pct=0.0, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, norm_eps=1e-6,
    tie_embeddings=True, dtype="float32")


def _mamba_init(block, key):
    """Mamba's own init of the per-channel constants (``init_params``
    leaves them constant): A_log = log(1..N), D = 1, the dt bias the
    inverse softplus of a dt log-uniform in [1e-3, 1e-1]; norm gains
    and the conv bias drawn so that the comparison sees them."""
    k = jax.random.split(key, 5)
    g, di, n = block["a_log"].shape
    dt = jnp.exp(jax.random.uniform(k[0], (g, di), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    out = dict(block)
    out["a_log"] = jnp.broadcast_to(jnp.log(jnp.arange(1.0, n + 1)),
                                    (g, di, n))
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    out["conv_b"] = 0.1 * jax.random.normal(k[1], (g, di))
    for i, name in enumerate(("dt_norm", "b_norm", "c_norm")):
        out[name] = 1 + 0.1 * jax.random.normal(k[2 + i], block[name].shape)
    return out


@pytest.fixture(scope="module")
def params():
    p = init_params(model_spec(CFG), jax.random.PRNGKey(3))
    for i, kind in enumerate(CFG.block_pattern):
        if kind == "mamba":
            pos = p["blocks"][f"pos{i}"]
            pos["block"] = _mamba_init(pos["block"], jax.random.PRNGKey(i))
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


def _decode_errors(params, seq, n_prompt):
    """Largest |program - reference| of the prefill's logits and of each
    decode step's, the decode running from the cache the engine's prefill
    made, in slot 1 of two, at its own position."""
    want = np.asarray(ref.forward(params, seq, CFG))
    got, _ = prefill(params, {"tokens": jnp.asarray(seq[:n_prompt])[None]},
                     CFG, max_len=32, cache_dtype=jnp.float32)
    errs = [float(np.max(np.abs(np.asarray(got[0, :CFG.vocab])
                                - want[n_prompt - 1])))]
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                       cache_dtype=jnp.float32)
    state = eng.insert(eng.prefill(None, seq[:n_prompt]), eng.init_state(),
                       slot=1)
    step = jax.jit(lambda c, t, p: decode_step(params, c, t, p, CFG))
    cache = state.cache
    for pos in range(n_prompt, len(seq)):
        logits, cache = step(cache, jnp.asarray([0, seq[pos]]),
                             jnp.asarray([3, pos], jnp.int32))
        errs.append(float(np.max(np.abs(np.asarray(logits[1, :CFG.vocab])
                                        - want[pos]))))
    return errs, want


def test_engine_prefill_then_decode_matches_reference(params):
    """Prefill 13 tokens through the engine, then decode 7 through its
    slot state (the conv window, the SSM state and the two attention
    layers' KV rows); each step's logits against the reference's full
    forward over the whole sequence."""
    errs, want = _decode_errors(params, _tokens(20, seed=5), 13)
    assert max(errs) <= TOL, errs
    assert np.std(want) > 0.05          # the logits are not flat


def test_bf16_state_control_fails_the_tolerance(params, monkeypatch):
    """The same comparison with the scan's inputs and the SSM state
    rounded to bfloat16 (in prefill and in every decode step) fails it."""
    bf16 = jnp.bfloat16

    def rounded(z):
        return z.astype(bf16).astype(jnp.float32)

    def scan_bf16(x, delta, a, b, c, d, state0=None, **kw):
        y, h = mamba_scan_ref(*(rounded(z) for z in (x, delta, a, b, c, d)))
        return y, rounded(h)

    step = mamba_mod._ssm_step

    def step_bf16(x, delta, a, b, c, d, h):
        y, h = step(x, delta, a, b, c, d, rounded(h))
        return y, rounded(h)

    monkeypatch.setattr(mamba_mod, "mamba_scan", scan_bf16)
    monkeypatch.setattr(mamba_mod, "_ssm_step", step_bf16)
    errs, _ = _decode_errors(params, _tokens(20, seed=5), 13)
    assert min(errs) > 10 * TOL, errs


def test_served_tokens_are_the_reference_best(params):
    """Server.submit_decode -> DecodeEngine -> cached graphs, two requests
    in two slots: each served token's reference logit lies within TOL of
    the reference's best at its position; one prefill graph per prompt
    length and one decode graph."""
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                       cache_dtype=jnp.float32)
    server = Server((), workers=(), engine=eng)
    prompts = [_tokens(11, seed=11), _tokens(16, seed=16)]
    rids = [server.submit_decode(p, 6) for p in prompts]
    server.flush()
    for prompt, rid in zip(prompts, rids):
        out = np.asarray(server.result(rid)[0])
        want = np.asarray(ref.forward(
            params, np.concatenate([prompt, out[:-1]]), CFG))
        rows = want[len(prompt) - 1:]
        assert np.max(rows.max(-1) - rows[np.arange(len(out)), out]) <= TOL
    assert eng.cache.misses == 3


def test_released_slot_takes_a_new_request_whole(params):
    """A request inserted into a released slot decodes what it decodes in
    a fresh engine: the insert replaced the slot's KV rows, conv window
    and SSM state together, nothing of the last request stays."""
    first, second = _tokens(14, seed=1), _tokens(9, seed=2)
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                       cache_dtype=jnp.float32)
    state = eng.insert(eng.prefill(None, first), eng.init_state(), slot=0)
    for _ in range(4):
        state, _ = eng.generate(None, state)
    state = eng.release(state, 0)
    state = eng.insert(eng.prefill(None, second), state, slot=0)
    fresh = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                         cache_dtype=jnp.float32)
    alone = fresh.insert(fresh.prefill(None, second), fresh.init_state(),
                         slot=0)
    for _ in range(5):
        state, got = eng.generate(None, state)
        alone, want = fresh.generate(None, alone)
        assert got[0] == want[0]


def test_mamba_block_matches_reference_with_its_norms(params):
    """One Mamba layer of the program (``mamba_full``: conv, x_proj, the
    dt/B/C RMSNorms, dt_proj, the scan, the gate) equals the reference
    mixer; without the norms it would not."""
    block = jax.tree_util.tree_map(lambda a: a[1],
                                   params["blocks"]["pos2"]["block"])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 37, CFG.d_model))
    got = mamba_full(block, x, CFG)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(block, x[0], CFG)
        plain = ref.mamba(dict(block, dt_norm=jnp.ones_like(block["dt_norm"]),
                               b_norm=jnp.ones_like(block["b_norm"]),
                               c_norm=jnp.ones_like(block["c_norm"])),
                          x[0], CFG)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=TOL, atol=TOL)
    assert float(jnp.max(jnp.abs(plain - want))) > 100 * TOL


def test_pallas_scan_equals_xla_and_reference():
    """The Pallas selective scan (interpret mode), the XLA chunked scan
    and the sequential reference agree at a length that is no multiple of
    the kernel's 128-step chunk, at Jamba's 16 state entries: outputs and
    the final state (which prefill hands to decode)."""
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t, dm, n = 1, 200, 256, 16
    x = jax.random.normal(k[0], (b, t, dm))
    delta = jax.nn.softplus(jax.random.normal(k[1], (b, t, dm)) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (dm, n))
    bm, cm = jax.random.normal(k[2], (b, t, n)), jax.random.normal(k[3], (b, t, n))
    d = jax.random.normal(k[4], (dm,))
    want_y, want_h = mamba_scan_ref(x, delta, a, bm, cm, d)
    for impl in ("pallas", "xla"):
        y, h = mamba_scan(x, delta, a, bm, cm, d, impl=impl)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                                   rtol=1e-4, atol=1e-4)


def test_no_rope_is_the_identity():
    """``rotary_pct`` 0 leaves queries and keys as they are, bit for bit:
    Jamba's attention has no positional encoding, and no option is added
    for it."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 9, 32))
    np.testing.assert_array_equal(
        np.asarray(apply_rotary(x, jnp.arange(9), 1e4, 0.0)), np.asarray(x))
    assert CFG.rotary_pct == 0.0


def test_engine_counts_state_by_kind(params):
    """The engine holds three kinds of slot state, KV rows beside the
    mamba conv windows and SSM states, publishes them by kind, and counts
    what each insert writes."""
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                       cache_dtype=jnp.float32)
    held = eng.slot_state_bytes()
    di, n = CFG.mamba_d_inner, CFG.mamba_d_state
    assert held == {"kv": 2 * 2 * 2 * 32 * 32 * 4,       # layers, k and v
                    "conv": 26 * 2 * 3 * di * 4,
                    "ssm": 26 * 2 * n * di * 4}
    state = eng.init_state()
    for slot in (0, 1):
        state = eng.insert(eng.prefill(None, _tokens(7, seed=slot)), state,
                           slot=slot)
    assert eng.state_written_bytes == held
    reg = MetricsRegistry()
    eng.publish_metrics(reg)
    for kind, nbytes in held.items():
        assert reg.get("repro_engine_slot_state_bytes").value(
            kind=kind) == nbytes
        assert reg.get("repro_engine_state_written_bytes_total").value(
            kind=kind) == nbytes
