"""Continuous-batching decode: engine slots vs naive rebatch-per-step.

ISSUE 9's tentpole claim is that the :class:`~repro.serve.DecodeEngine`
turns steady-state autoregressive decode into the replay of ONE cached
``CommandGraph``: the batched decode state stays resident on the lane
(donated back into every launch), so a step's host traffic is exactly the
token/position I/O.  The naive baseline — rebatching per step, which
round-trips the whole KV cache through the host both ways every token —
is the SAME engine priced with ``resident=False``; both arms decode the
same staggered workload bit-identically, so the modeled tokens/s ratio
isolates residency, and CI gates it at >= 1.3x (deterministic: machine
model, never wall clock).

The roofline readout comes straight off the captured schedule
(:class:`~repro.serve.EngineRoofline`): bytes/step, the bandwidth-floor
step time, and how memory-bound the step is.

Results append to ``BENCH_serve.json`` tagged ``bench="decode"``.
"""

import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.models import init_params, model_spec
from repro.serve import DecodeEngine
from repro.train.serve import greedy_generate

from .history import append_entry

ARCH = "qwen2.5-3b"
SLOTS = 4
N_REQ = 8          # staggered: 2x oversubscribed so slots churn
PROMPT = 12
NEW = 6            # tokens per request (1 from prefill + NEW-1 decode steps)
MAX_LEN = 96       # serving-sized KV allocation (what the naive arm moves)
OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_serve.json"


def _workload(eng, prompts):
    """Drain N_REQ staggered requests through the engine's slots."""
    state = eng.init_state()
    pending = list(range(len(prompts)))
    live = {}                                  # slot -> (req, remaining)
    outs = {}
    while pending or live:
        for slot in state.free_slots():
            if not pending:
                break
            r = pending.pop(0)
            pre = eng.prefill(None, prompts[r])
            state = eng.insert(pre, state, slot)
            live[slot] = (r, NEW - 1)
            outs[r] = [int(pre.token[0])]
        state, toks = eng.generate(None, state)
        for slot in list(live):
            r, rem = live[slot]
            outs[r].append(int(toks[slot]))
            if rem - 1 == 0:
                state = eng.release(state, slot)
                del live[slot]
            else:
                live[slot] = (r, rem - 1)
    return outs


def _arm(cfg, params, prompts, *, resident):
    eng = DecodeEngine(cfg, params, num_slots=SLOTS,
                       max_len=MAX_LEN, resident=resident)
    outs = _workload(eng, prompts)             # warm: captures both graphs
    t0 = time.perf_counter()
    outs2 = _workload(eng, prompts)            # steady state: replay only
    wall = time.perf_counter() - t0
    assert outs == outs2, "decode is deterministic"
    assert eng.cache.misses == 2, eng.cache.stats()
    return eng, outs, wall


def run():
    print("=" * 76)
    print("Continuous-batching decode: resident slots vs rebatch-per-step")
    print(f"({ARCH} reduced, {N_REQ} staggered requests x {NEW} tokens on "
          f"{SLOTS} slots)")
    print("=" * 76)
    cfg = ARCHS[ARCH].reduced()
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    prompts = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (N_REQ, PROMPT)),
        jnp.int32)
    ref = np.asarray(greedy_generate(params, cfg, prompts, max_new=NEW,
                                     max_len=PROMPT + NEW + 1))

    engine, outs_e, wall_e = _arm(cfg, params, prompts, resident=True)
    naive, outs_n, _ = _arm(cfg, params, prompts, resident=False)

    # honesty first: both arms must deliver the whole-batch greedy bits
    for r in range(N_REQ):
        assert outs_e[r] == list(ref[r]), (r, outs_e[r], list(ref[r]))
    assert outs_n == outs_e, "naive arm diverged from engine arm"

    tps_e = engine.tokens_per_s_modeled
    tps_n = naive.tokens_per_s_modeled
    ratio = tps_e / tps_n
    roof = engine.roofline()
    wall_tps = engine.n_tokens / 2 / wall_e    # stats span both workloads
    print(f"  engine (resident)   {tps_e:12.0f} tok/s modeled   "
          f"occupancy {engine.occupancy:.0%}")
    print(f"  naive rebatch/step  {tps_n:12.0f} tok/s modeled")
    print(f"  wall (steady state) {wall_tps:12.0f} tok/s")
    print(f"\n  resident decode is {ratio:.2f}x the rebatch-per-step "
          f"baseline (>= 1.3x CI gate)")
    print(f"  roofline: {roof.bytes_per_step:,.0f} B/step -> "
          f"{roof.min_step_s * 1e6:.1f} us bandwidth floor, "
          f"{roof.mem_bound_fraction:.0%} memory-bound")

    result = {
        "bench": "decode",
        "arch": ARCH,
        "slots": SLOTS,
        "n_requests": N_REQ,
        "tokens_per_request": NEW,
        "tokens_per_s_modeled": {"engine": tps_e, "naive_rebatch": tps_n},
        "resident_vs_rebatch_speedup": ratio,
        "wall_tokens_per_s": wall_tps,
        "occupancy": engine.occupancy,
        "roofline": {
            "bytes_per_step": roof.bytes_per_step,
            "min_step_s": roof.min_step_s,
            "mem_bound_fraction": roof.mem_bound_fraction,
            "modeled_step_s": roof.modeled_step_s,
        },
        "bit_identical_to_greedy": True,
        "cache_stats": engine.cache.stats(),
    }
    history = append_entry(OUT_PATH, result)
    print(f"  appended to {OUT_PATH.name} (run #{len(history)})")
    return result


if __name__ == "__main__":
    run()
