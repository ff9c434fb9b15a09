"""Serving example: autoregressive LM decode through the ISSUE 9
continuous-batching engine — ``Server(engine=DecodeEngine(...))`` with
per-request streaming, ending in a :class:`ServeReport` printout.

A reduced GQA transformer (plain KV cache) serves a staggered stream of
prompts over a handful of decode slots: each request is prefilled
batch-1, spliced into a free slot of the persistent batched decode state,
and advanced one token per step by the replay of ONE cached
``CommandGraph`` — freed slots admit the next waiting request
mid-generation, and ``Server.stream`` yields each request's tokens as its
steps land.  The example doubles as a living integration test: it asserts
that

* the warm engine performs ZERO re-captures (one prefill graph + one
  decode graph, every launch after that a GraphCache hit), and
* every streamed result is bit-identical to eager whole-batch
  ``greedy_generate`` — slot insertion never perturbs a neighbor.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, model_spec
from repro.serve import DecodeEngine, Server
from repro.train.serve import greedy_generate

ARCH = "qwen2.5-3b"
SLOTS = 4
N_REQUESTS = 12      # 3x oversubscribed: slots churn mid-generation
PROMPT = 12
MAX_NEW = 8
MAX_LEN = PROMPT + MAX_NEW + 1


def main():
    enable_compile_cache()
    cfg = ARCHS[ARCH].reduced()
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    prompts = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab,
                                          (N_REQUESTS, PROMPT)),
        jnp.int32)

    engine = DecodeEngine(cfg, params, num_slots=SLOTS, max_len=MAX_LEN)
    server = Server((), workers=(), engine=engine)

    # -- submit everything up front; stream one request while it decodes ----
    t0 = time.perf_counter()
    rids = [server.submit_decode(prompts[i], max_new=MAX_NEW)
            for i in range(N_REQUESTS)]
    streamed = list(server.stream(rids[0]))    # live per-step iterator
    server.flush()                             # drain the remaining slots
    wall = time.perf_counter() - t0

    # -- zero re-capture: ONE prefill graph + ONE decode graph --------------
    assert engine.cache.misses == 2, (
        f"engine re-captured a graph: {engine.cache.stats()}")

    # -- streamed == eager whole-batch greedy decode, bit for bit -----------
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=MAX_NEW,
                                     max_len=MAX_LEN))
    assert streamed == [int(t) for t in ref[0]], (
        "streamed tokens diverged from eager greedy decode")
    for i, rid in enumerate(rids):
        (got,) = server.result(rid)
        assert np.array_equal(np.asarray(got), ref[i]), (
            f"request {rid}: engine decode diverged from eager greedy")

    report = server.report()
    print("=" * 72)
    print(f"serve_lm: {N_REQUESTS} requests x {MAX_NEW} tokens ({ARCH} "
          f"reduced) on {SLOTS} decode slots")
    print("=" * 72)
    print(report.summary())
    print(f"\n{N_REQUESTS * MAX_NEW / wall:,.0f} tok/s wall incl. capture, "
          f"occupancy {report.engine_slot_occupancy:.0%}")
    print("\nserve_lm OK — warm engine re-captured nothing; streamed "
          "results bit-identical to eager greedy decode")
    return report


if __name__ == "__main__":
    main()
