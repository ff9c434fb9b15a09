"""DeepSeek-V2 as one chip of an expert-parallel deployment, against the
plain reference (``repro.models.reference_deepseek_v2``) on seeded random
weights, at the published structure with every width divided by 16: 160
routed experts in 8 groups, top 3 groups, 6 experts a token, 2 shared
experts, YaRN, and this chip holding group 0 (experts 0-19).

The program runs in float32 here, so each tolerance only has to cover the
order in which float32 sums are taken (flash attention's online softmax,
the absorbed decode, the grouped matmul's tiles): 2e-4 on logits of size
about 1.  A route flip (two experts' scores within rounding) would show as
a logit off by far more, and none occurs at these seeds.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import init_params, model_spec
from repro.models import reference_deepseek_v2 as ref
from repro.models.layers import yarn_frequencies, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models.moe import apply_moe_serve, route
from repro.models.transformer import cache_struct, decode_step, prefill
from repro.obs import MetricsRegistry
from repro.serve import DecodeEngine, Server

#: logits agree to this (float32 program against the float32 reference)
TOL = 2e-4


def small(**kw):
    """The published config with every width divided by 16 and the
    benchmark's cut: 5 layers, group 0 held, a sixteenth of the vocab."""
    base = dict(name="deepseek-v2-small", n_layers=5, d_model=320,
                n_heads=8, n_kv_heads=8, head_dim=16, d_ff=96, vocab=800,
                q_lora_rank=96, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=16, v_head_dim=16, d_ff_expert=96,
                d_ff_dense=768, experts_held=20, dtype="float32")
    base.update(kw)
    return dataclasses.replace(ARCHS["deepseek-v2-236b"], **base)


CFG = small()


@pytest.fixture(scope="module")
def params():
    p = init_params(model_spec(CFG), jax.random.PRNGKey(7))
    # a wider router spreads the gate's scores, so that routing is decided
    # by more than rounding
    blocks = p["blocks"]["pos0"]["mlp"]
    blocks["router"] = blocks["router"] * 8.0
    return p


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(
        np.int32)


# -- the published formulas, transcribed with numpy -------------------------

def _np_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return inter * (1 - mask) + extra * mask


def test_yarn_matches_published_formulas():
    cfg = ARCHS["deepseek-v2-236b"]
    want = _np_yarn(64, 1e4, 40, 4096, 32, 1)
    np.testing.assert_allclose(np.asarray(yarn_frequencies(64, cfg)), want,
                               rtol=1e-6)
    # channels 0-9 keep base^(-2i/d), 23-31 are divided by 40
    assert want[9] == pytest.approx(1e4 ** (-18 / 64))
    assert want[23] == pytest.approx(1e4 ** (-46 / 64) / 40)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def _np_gate(scores, n_group, topk_group, k, norm, factor):
    t, e = scores.shape
    group_scores = scores.reshape(t, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=1, kind="stable")[:,
                                                                 :topk_group]
    mask = np.zeros((t, n_group))
    np.put_along_axis(mask, group_idx, 1, axis=1)
    tmp = np.where(np.repeat(mask, e // n_group, axis=1) > 0, scores, 0.0)
    idx = np.argsort(-tmp, axis=1, kind="stable")[:, :k]
    w = np.take_along_axis(tmp, idx, axis=1)
    if k > 1 and norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        w = w * factor
    return w, idx


def test_routing_matches_published_gate():
    cfg = ARCHS["deepseek-v2-236b"]
    logits = np.random.default_rng(3).standard_normal((64, 160)) * 3
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
    want_w, want_e = _np_gate(scores, 8, 3, 6, False, 16.0)
    got_w, got_e = route(jnp.asarray(logits, jnp.float32), cfg)
    np.testing.assert_array_equal(np.asarray(got_e), want_e)
    np.testing.assert_allclose(np.asarray(got_w), want_w, rtol=1e-5)
    # every pick lies in one of the token's 3 eligible groups
    assert all(len(set(r // 20)) <= 3 for r in np.asarray(got_e))
    # the renormalised rule of the other MoE configurations
    norm = dataclasses.replace(cfg, n_group=1, topk_group=1,
                               norm_topk_prob=True)
    w, _ = route(jnp.asarray(logits, jnp.float32), norm)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("length", [9, 24])
def test_prefill_logits_match_reference(params, length):
    toks = _tokens(length, seed=length)
    want, routed = ref.forward(params, toks, CFG)
    got, _, experts = prefill(params, {"tokens": jnp.asarray(toks)[None]},
                              CFG, max_len=32, cache_dtype=jnp.float32,
                              return_experts=True)
    np.testing.assert_allclose(np.asarray(got[0, :CFG.vocab]),
                               np.asarray(want[-1]), rtol=TOL, atol=TOL)
    np.testing.assert_array_equal(np.sort(np.asarray(experts[:, 0]), -1),
                                  np.sort(np.stack(routed), -1))


def test_prefill_then_decode_matches_reference_forward(params):
    """Prefill 12 tokens, then decode 6 through the latent cache (the
    absorbed form); each step's logits against the reference's full
    forward pass over the whole sequence."""
    seq = _tokens(18, seed=5)
    want, _ = ref.forward(params, seq, CFG)
    _, cache = prefill(params, {"tokens": jnp.asarray(seq[:12])[None]}, CFG,
                       max_len=20, cache_dtype=jnp.float32)
    step = jax.jit(lambda c, t, p: decode_step(params, c, t, p, CFG))
    for pos in range(12, 18):
        logits, cache = step(cache, jnp.asarray(seq[pos:pos + 1]),
                             jnp.int32(pos))
        np.testing.assert_allclose(np.asarray(logits[0, :CFG.vocab]),
                                   np.asarray(want[pos]), rtol=TOL, atol=TOL)


def test_served_tokens_and_counters_match_reference(params):
    """Server.submit_decode -> DecodeEngine -> cached graphs: each served
    token is the reference's best at its position (within TOL of it), and
    the engine's counters count what the reference routes."""
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32,
                       cache_dtype=jnp.float32)
    server = Server((), workers=(), engine=eng)
    prompts = [_tokens(11, seed=11), _tokens(16, seed=16)]
    rids = [server.submit_decode(p, 5) for p in prompts]
    server.flush()
    outs = [np.asarray(server.result(r)[0]) for r in rids]
    for prompt, out in zip(prompts, outs):
        seq = np.concatenate([prompt, out[:-1]])
        want, _ = ref.forward(params, seq, CFG)
        rows = np.asarray(want[len(prompt) - 1:])
        best = rows.max(-1)
        chosen = rows[np.arange(len(out)), out]
        assert np.max(best - chosen) <= TOL
    assert eng.n_prefills == 2 and eng.cache.misses <= 3
    assert 0 < eng.moe_rows_routed <= eng.moe_rows_computed
    reg = server.publish_metrics(MetricsRegistry())
    moe = reg.get("repro_moe_events_total")
    assert moe.value(kind="rows_routed") == eng.moe_rows_routed
    assert moe.value(kind="rows_computed") == eng.moe_rows_computed
    assert moe.value(kind="experts_touched") == eng.moe_experts_touched


def _held_rows(experts, first=0, held=20):
    """Routed experts (..., k) of one layer -> rows per held expert."""
    local = np.asarray(experts).ravel() - first
    return np.bincount(local[(local >= 0) & (local < held)], minlength=held)


def test_engine_prefill_rows_equal_reference_routed_count(params):
    toks = _tokens(20, seed=20)
    eng = DecodeEngine(CFG, params, num_slots=2, max_len=32)
    eng.prefill(None, toks)
    _, routed = ref.forward(params, toks, CFG)
    np.testing.assert_array_equal(
        np.sort(eng.moe_last_experts, -1),
        np.sort(np.stack(routed).transpose(1, 0, 2), -1))
    rows = np.stack([_held_rows(e) for e in routed])
    np.testing.assert_array_equal(eng.moe_last_rows, rows)
    assert eng.moe_rows_routed == int(rows.sum())
    assert eng.moe_experts_touched == int((rows > 0).sum())
    assert eng.moe_rows_routed <= eng.moe_rows_computed


def test_eight_shares_add_up_to_the_uncut_layer(params):
    """Model-configs section 4: the 8 shares, each holding one group of 20
    experts, summed with the shared experts counted once, equal the uncut
    reference layer over all 160 experts."""
    uncut = small(experts_held=160)
    full = init_params(model_spec(uncut), jax.random.PRNGKey(11))
    layer = jax.tree_util.tree_map(lambda a: a[0], full["blocks"]["pos0"])
    layer["mlp"]["router"] = layer["mlp"]["router"] * 8.0
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, uncut.d_model))
    with jax.default_matmul_precision("highest"):
        want, experts_all = ref.moe(layer["mlp"], h[0], uncut)
        shared = ref.mlp(layer["mlp"]["shared"], h[0])
    total = -7 * shared
    rows = []
    for g in range(8):
        share = small(expert_first=20 * g)
        p = dict(layer["mlp"])
        for k in ("wi", "wg", "wo"):
            p[k] = layer["mlp"][k][20 * g: 20 * (g + 1)]
        y, experts = apply_moe_serve(p, h, share)
        total = total + y[0]
        np.testing.assert_array_equal(np.sort(np.asarray(experts[0]), -1),
                                      np.sort(np.asarray(experts_all), -1))
        rows.append(_held_rows(experts, first=20 * g))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    rows_all = _held_rows(experts_all, held=160)
    np.testing.assert_array_equal(np.concatenate(rows), rows_all)
    assert int(rows_all.sum()) == 24 * 6


def _gmm_rows(fn, *args):
    """The row count of every grouped matmul (``moe_gmm`` call) that
    ``fn`` traces, in trace order."""
    rows = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.params.get("name") == "moe_gmm":
                rows.append(eqn.invars[0].aval.shape[0])
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return rows


def test_decode_lanes_share_one_grouped_matmul(params):
    """A decode step hands the MoE layer every slot's token at once, at
    each slot's own position, so the tokens of all slots go through ONE
    grouped matmul per projection; each slot still gets its own routing
    and the output it would get alone (to float32 rounding: the batched
    matmuls over five rows sum in another order than five one-row ones)."""
    layer = jax.tree_util.tree_map(lambda a: a[0],
                                   params["blocks"]["pos0"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(4), (5, 1, CFG.d_model))
    y, experts = apply_moe_serve(layer, h, CFG)
    assert experts.shape == (5, 1, CFG.top_k)
    for i in range(5):
        yi, ei = apply_moe_serve(layer, h[i:i + 1], CFG)
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(yi[0]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(experts[i]),
                                      np.asarray(ei[0]))
    five = _gmm_rows(lambda x: apply_moe_serve(layer, x, CFG), h)
    one = _gmm_rows(lambda x: apply_moe_serve(layer, x, CFG), h[:1])
    cache = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        cache_struct(CFG, 5, 32))
    step = _gmm_rows(lambda c, t, p: decode_step(params, c, t, p, CFG),
                     cache, jnp.zeros((5,), jnp.int32),
                     jnp.arange(5, dtype=jnp.int32) + 20)
    assert len(five) == 3 and five[0] > one[0]
    assert step == five
