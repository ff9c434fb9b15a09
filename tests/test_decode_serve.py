"""Greedy decode across the four cache families (repro.train.serve).

The old ``examples/serve_lm.py`` was the only executable coverage of
``greedy_generate`` and the per-family decode caches; when that example was
repurposed for the ``repro.serve`` engine (ISSUE 2), this test inherited
the coverage: a GQA transformer (plain KV cache), the MLA+MoE family
(compressed latent cache), the attention-free rwkv6 (O(1) state) and the
jamba hybrid (mamba conv and SSM state beside a KV cache) all decode
through one serving API.

ISSUE 9 layers the continuous-batching :class:`DecodeEngine` on top and
pins its invariants here: slot-based decode is bit-identical to whole-batch
``greedy_generate`` for every cache family — including staggered
mid-generation insertion and slot reuse — off exactly ONE cached decode
graph (plus one prefill graph), whose step outputs carry no ``(B, vocab)``
logits; the :class:`~repro.serve.Server` streaming front and the asyncio
HTTP ingress deliver the same bits.
"""

import asyncio
import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import init_params, model_spec
from repro.models.transformer import decode_step, prefill
from repro.obs import Tracer
from repro.serve import DecodeEngine, EngineHTTPServer, Server
from repro.serve.faults import InjectedFault
from repro.serve.engine import batch_axes
from repro.serve.server import AdmissionError
from repro.train.serve import greedy_generate

BATCH, PROMPT, NEW = 2, 12, 4

FAMILIES = ["qwen2.5-3b",       # GQA: plain KV cache
            "deepseek-v2-236b",  # MLA latent cache
            "rwkv6-3b",          # O(1) recurrent state
            "jamba-1.5-large-398b"]  # mamba state beside a KV cache


def _setup(arch, batch, prompt_len, seed=1):
    cfg = ARCHS[arch].reduced()
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    prompts = jnp.asarray(
        np.random.default_rng(seed).integers(0, cfg.vocab,
                                             (batch, prompt_len)),
        jnp.int32)
    return cfg, params, prompts


@pytest.mark.parametrize("arch", FAMILIES)
def test_greedy_generate_cache_family(arch):
    cfg, params, prompts = _setup(arch, BATCH, PROMPT)
    out = greedy_generate(params, cfg, prompts, max_new=NEW,
                          max_len=PROMPT + NEW + 1)
    assert out.shape == (BATCH, NEW)
    assert bool(jnp.all((out >= 0) & (out < cfg.vocab_padded)))
    # greedy decoding is deterministic
    again = greedy_generate(params, cfg, prompts, max_new=NEW,
                            max_len=PROMPT + NEW + 1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


@pytest.mark.parametrize("arch", FAMILIES)
def test_positions_vector_step_equals_single_rows(arch):
    """One decode step with each row at its own position equals, row for
    row, a step that puts every row at that row's position as a scalar —
    exactly (float32 model and cache): logits, and every cache leaf of
    the row.  Both steps run the same batch, because a one-row matmul
    sums in another order than a batched one on the CPU."""
    cfg = ARCHS[arch].reduced()
    assert cfg.dtype == "float32"
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    lens, max_len = [5, 12, 9], 16
    rng = np.random.default_rng(3)
    caches = [prefill(params, {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (1, n)), jnp.int32)}, cfg, max_len,
        cache_dtype=jnp.float32)[1] for n in lens]
    bidx = batch_axes(cfg)
    batched = jax.tree_util.tree_map(
        lambda i, *cs: jnp.concatenate(cs, axis=i), bidx, *caches)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, len(lens)), jnp.int32)
    logits, cache = decode_step(params, batched, tokens,
                                jnp.asarray(lens, jnp.int32), cfg)
    for r, n in enumerate(lens):
        want, want_cache = decode_step(params, batched, tokens,
                                       jnp.int32(n), cfg)
        np.testing.assert_array_equal(np.asarray(logits[r]),
                                      np.asarray(want[r]))
        jax.tree_util.tree_map(
            lambda i, got, w: np.testing.assert_array_equal(
                np.asarray(jnp.take(got, r, axis=i)),
                np.asarray(jnp.take(w, r, axis=i))),
            bidx, cache, want_cache)


# -- ISSUE 9: the continuous-batching decode engine -------------------------

@pytest.mark.parametrize("arch", FAMILIES)
def test_engine_bit_identical_per_family(arch):
    """Engine slots == whole-batch greedy_generate, bit for bit, for every
    cache family — off exactly one prefill + one decode graph."""
    cfg, params, prompts = _setup(arch, BATCH, PROMPT)
    max_len = PROMPT + NEW + 1
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=NEW,
                                     max_len=max_len))
    eng = DecodeEngine(cfg, params, num_slots=BATCH, max_len=max_len)
    state = eng.init_state()
    for i in range(BATCH):
        state = eng.insert(eng.prefill(None, prompts[i]), state, slot=i)
    got = [np.asarray(state.tokens)]          # token 1 comes from prefill
    for _ in range(NEW - 1):
        state, toks = eng.generate(None, state)
        got.append(toks)
    np.testing.assert_array_equal(np.stack(got, axis=1), ref)
    # zero re-capture: ONE prefill graph + ONE decode graph, period
    assert eng.cache.misses == 2
    assert eng.cache.hits == (BATCH - 1) + (NEW - 2)


def test_engine_staggered_insert_and_slot_reuse():
    """A request spliced into a freed slot mid-generation decodes the same
    bits as the whole-batch reference, and never perturbs its neighbor."""
    cfg, params, prompts = _setup("qwen2.5-3b", 3, PROMPT)
    new_long = 6
    max_len = PROMPT + new_long + 1
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=new_long,
                                     max_len=max_len))
    eng = DecodeEngine(cfg, params, num_slots=2, max_len=max_len)
    state = eng.init_state()
    # r0 (short) and r1 (long) start together in slots 0/1
    state = eng.insert(eng.prefill(None, prompts[0]), state, slot=0)
    state = eng.insert(eng.prefill(None, prompts[1]), state, slot=1)
    out = {0: [int(state.tokens[0])], 1: [int(state.tokens[1])]}
    for _ in range(2):
        state, toks = eng.generate(None, state)
        out[0].append(int(toks[0]))
        out[1].append(int(toks[1]))
    # r0 finishes after 3 tokens; its slot is reused by r2 mid-generation
    state = eng.release(state, 0)
    state = eng.insert(eng.prefill(None, prompts[2]), state, slot=0)
    out[2] = [int(state.tokens[0])]
    for _ in range(new_long - 3):
        state, toks = eng.generate(None, state)
        out[2].append(int(toks[0]))
        out[1].append(int(toks[1]))
    assert out[0] == list(ref[0][:3])
    assert out[1] == list(ref[1])            # neighbor never perturbed
    assert out[2] == list(ref[2][:new_long - 2])
    assert eng.cache.misses == 2             # still just two graphs


def test_engine_decode_graph_carries_no_logits():
    """The per-step graph's outputs are tokens + cache only — no
    ``(num_slots, vocab)`` logits ride the hot decode loop."""
    cfg, params, prompts = _setup("qwen2.5-3b", 1, PROMPT)
    eng = DecodeEngine(cfg, params, num_slots=2, max_len=PROMPT + 4)
    state = eng.insert(eng.prefill(None, prompts[0]), eng.init_state(), 0)
    state, _ = eng.generate(None, state)
    assert eng.decode_graph is not None
    for aval in eng.decode_graph.out_avals:
        assert not (len(aval.shape) >= 2
                    and aval.shape[0] == eng.num_slots
                    and aval.shape[-1] == cfg.vocab_padded), (
            f"decode step leaked a logits-shaped output {aval.shape}")


def test_server_engine_streaming_front():
    """submit_decode/stream round-trip: bit-identical results, slot churn
    across more requests than slots, exactly one terminal span per rid."""
    cfg, params, prompts = _setup("qwen2.5-3b", 3, PROMPT)
    max_len = PROMPT + NEW + 1
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=NEW,
                                     max_len=max_len))
    tracer = Tracer()
    eng = DecodeEngine(cfg, params, num_slots=2, max_len=max_len)
    srv = Server((), workers=(), engine=eng, tracer=tracer)
    rids = [srv.submit_decode(prompts[i], max_new=NEW) for i in range(3)]
    # streaming one rid to completion drives the other slots forward too
    assert list(srv.stream(rids[0])) == [int(t) for t in ref[0]]
    srv.flush()
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(np.asarray(srv.result(rid)[0]), ref[i])
    rep = srv.report()
    # decode steps produce NEW-1 tokens/request (token 1 is prefill's)
    assert rep.engine_tokens == 3 * (NEW - 1)
    assert rep.engine_steps > 0
    assert 0.0 < rep.engine_slot_occupancy <= 1.0
    assert "engine" in rep.summary()
    # every accepted rid terminates in exactly one result/shed span
    for rid in rids:
        root = tracer.request_root(rid)
        terms = [s for s in tracer.children(root)
                 if s.name in ("result", "shed")]
        assert len(terms) == 1


def test_http_ingress_smoke():
    """The asyncio front door streams the same bits over chunked HTTP."""
    cfg, params, prompts = _setup("qwen2.5-3b", 2, PROMPT)
    max_len = PROMPT + NEW + 1
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=NEW,
                                     max_len=max_len))
    eng = DecodeEngine(cfg, params, num_slots=2, max_len=max_len)
    srv = Server((), workers=(), engine=eng)
    front = EngineHTTPServer(srv)

    async def post(host, port, prompt, max_new):
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt": [int(t) for t in prompt],
                           "max_new": max_new}).encode()
        writer.write(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode()
                     + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head.split(b" ")[1])
        toks = []
        while status == 200:
            n = int((await reader.readuntil(b"\r\n")).strip(), 16)
            if n == 0:
                break
            toks.append(int((await reader.readexactly(n + 2))[:-2]))
        writer.close()
        return status, toks

    async def run():
        host, port = await front.start()
        try:
            results = await asyncio.gather(
                *[post(host, port, prompts[i], NEW) for i in range(2)])
            bad = await post(host, port, [], NEW)    # empty prompt -> 400
            return results, bad
        finally:
            await front.stop()

    results, bad = asyncio.run(run())
    for i, (status, toks) in enumerate(results):
        assert status == 200
        assert toks == [int(t) for t in ref[i]]
    assert bad[0] == 400


def _fail_engine(monkeypatch, eng, phase, exc):
    def boom(*args, **kwargs):
        raise exc
    monkeypatch.setattr(eng, phase, boom)


@pytest.mark.parametrize("phase", ["prefill", "generate"])
def test_engine_injected_fault_sheds_loudly(phase, monkeypatch):
    """An injected launch fault sheds the request: result() names why."""
    cfg, params, prompts = _setup("qwen2.5-3b", 1, PROMPT)
    eng = DecodeEngine(cfg, params, num_slots=1, max_len=PROMPT + NEW + 1)
    srv = Server((), workers=(), engine=eng)
    if phase == "prefill":
        _fail_engine(monkeypatch, eng, phase, InjectedFault("lane down"))
    rid = srv.submit_decode(prompts[0], max_new=NEW)
    if phase == "generate":
        _fail_engine(monkeypatch, eng, phase, InjectedFault("lane down"))
    srv.flush()
    with pytest.raises(AdmissionError, match=f"engine {phase} failed"):
        srv.result(rid)


@pytest.mark.parametrize("phase", ["prefill", "generate"])
def test_engine_real_error_propagates(phase, monkeypatch):
    """A failure that is no injected fault (a compiler or memory error, a
    bug) reaches the caller instead of ending as a shed request."""
    cfg, params, prompts = _setup("qwen2.5-3b", 1, PROMPT)
    eng = DecodeEngine(cfg, params, num_slots=1, max_len=PROMPT + NEW + 1)
    srv = Server((), workers=(), engine=eng)
    err = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    if phase == "prefill":
        _fail_engine(monkeypatch, eng, phase, err)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            srv.submit_decode(prompts[0], max_new=NEW)
    else:
        rid = srv.submit_decode(prompts[0], max_new=NEW)
        _fail_engine(monkeypatch, eng, phase, err)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            list(srv.stream(rid))
    assert srv.n_shed == 0


# -- the dense decoder's served numbers, bit for bit ---------------------------

#: qwen2.5-3b ``.reduced()`` in each dtype: a SHA-256 prefix of the logits
#: of a batch-2 prefill of 12 tokens and 4 greedy decode steps after it,
#: and the final cache; and the tokens ``Server`` -> ``DecodeEngine``
#: serves to two staggered prompts.  Recorded (XLA:CPU, x86-64) from the
#: serving path as it stood before YaRN, the layer index of the serving
#: scans and the MoE layers' routed-expert outputs joined the code that
#: qwen2.5-3b shares with DeepSeek-V2; a change that moves any bit of the
#: dense decoder's served numbers fails here.  The digests depend on
#: XLA:CPU's code generation, so a new JAX records them anew.
QWEN_SERVED = {
    "float32": ("163786422f4ceed3", [[485, 485, 485, 144, 241, 241],
                                     [372, 63, 72, 427, 67, 67]]),
    "bfloat16": ("f7c240c58f50d9b6", [[485, 485, 485, 144, 241, 241],
                                      [372, 63, 72, 427, 67, 67]]),
}


@pytest.mark.parametrize("dtype", sorted(QWEN_SERVED))
def test_qwen_served_numbers_unchanged(dtype):
    cfg = dataclasses.replace(ARCHS["qwen2.5-3b"].reduced(), dtype=dtype)
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab,
                                                         (2, 12)), jnp.int32)
    logits, cache = prefill(params, {"tokens": toks}, cfg, max_len=20)
    steps = [logits]
    for pos in range(12, 16):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        logits, cache = decode_step(params, cache, nxt, jnp.int32(pos), cfg)
        steps.append(logits)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves((steps, cache)):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    want_digest, want_tokens = QWEN_SERVED[dtype]
    assert h.hexdigest()[:16] == want_digest
    eng = DecodeEngine(cfg, params, num_slots=2, max_len=24)
    server = Server((), workers=(), engine=eng)
    rids = [server.submit_decode(np.asarray(toks[i, : 8 + 4 * i]), 6)
            for i in range(2)]
    server.flush()
    got = [np.asarray(server.result(r)[0]).tolist() for r in rids]
    assert got == want_tokens
