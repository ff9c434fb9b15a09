#!/usr/bin/env python3
"""Chip smoke test: drive the served paths once on a TPU and check them.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # only the sharded TinyBio/GeMM lane

Everything runs in this one process, which holds the chip.  Phases, in order
(each prints one line with its wall seconds and the seconds JAX spent
compiling inside it):

* ``device`` -- JAX sees a TPU and Pallas kernels take the Mosaic path (no
  interpret mode).  Anything else exits nonzero here and prints no result.
* ``paper`` -- the paper's int32 GeMM offloaded through ``APU``/``Program``
  at 256x256 and 1024x1024, exact against numpy; the TinyBio pipeline at the
  paper's workload served through ``Server`` on a ``QueueWorker`` lane, each
  stage checked against its ``ref.py`` oracle.  Every Pallas stage must
  compile to a ``tpu_custom_call``.
* ``lm`` -- qwen2.5-3b at its published widths, random bf16 weights, served
  by ``DecodeEngine`` behind ``Server``: 8 seeded requests (prompts of 128
  and 512 tokens, 32 new tokens each), served twice (cold, then warm), and
  checked against ``greedy_generate`` on the same prompts.

``--chips 4`` runs only the multi-chip path: a ``ShardedWorker`` over a
4-device ``data_mesh`` serving the TinyBio and GeMM buckets, next to a
single-device lane in the same process, and compares the two.

The last line of standard output is ``{"ok": true, "device": {...}}``.  A
failed check or phase is reported where it happens and the run goes on, so
that one run shows every failure; the script then exits nonzero without that
line.  The persistent compilation cache is placed by
``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

SEED = 0
GEMM_SIDES = (256, 1024)
LM_ARCH = "qwen2.5-3b"
LM_SLOTS = 4
LM_MAX_LEN = 1024
LM_MAX_NEW = 32
LM_PROMPT_LENS = (128, 512)
LM_REQUESTS = 8
#: graphs a warm engine holds: one prefill per prompt length + one decode
LM_GRAPHS = len(LM_PROMPT_LENS) + 1
#: a divergence from greedy_generate is accepted only where the reference's
#: own choice beats the engine's by at most this many bf16 spacings of the
#: winning logit.  On a TPU the engine's batched decode step and the batch-1
#: reference compile to differently tiled bf16 matmuls, so their logits
#: differ by rounding, and random weights give near-flat logits whose top
#: two often tie at bf16 resolution: ties and one-spacing gaps are the
#: divergences that rounding explains.
LOGIT_TIE_ULPS = 1
#: (rtol, atol) of each TinyBio stage against its ref.py oracle: the
#: tolerances the repository's kernel tests hold each kernel to
STAGE_TOL = {"fir": (2e-4, 2e-4), "delineate_keep": (0.0, 0.0),
             "fft_features": (1e-3, 1e-3), "svm": (1e-4, 1e-4)}
PIPELINE_TOL = (1e-4, 1e-5)
SHARDED_CHIPS = 4


class PhaseClock:
    """Wall seconds and JAX compile seconds of each phase.

    Compile time is the sum of JAX's backend-compile durations (a
    persistent-cache hit counts its retrieval time), read from
    ``jax.monitoring`` events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name):
        c0, n0, h0 = self.compile_s, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        print(f"[{name}] wall {wall} s, compile {self.compile_s - c0} s "
              f"({self.compiles - n0} compiles, "
              f"{self.cache_hits - h0} persistent-cache hits) -- chip "
              "numbers", flush=True)


def run_phase(clock, name, fn, *args):
    """Run one phase; an error fails it, and the later phases still run."""
    with clock.phase(name):
        try:
            fn(*args)
        except Exception as e:
            traceback.print_exc()
            fail(f"phase {name}: {type(e).__name__}: {e}")


def require_mosaic(jax, name, fn, *args, compiled=True):
    """``fn`` at ``args`` holds at least one Mosaic kernel: in the compiled
    program, or (``compiled=False``, for programs too large to compile a
    second time) in the lowered one."""
    lowered = jax.jit(fn).lower(*args)
    text = (lowered.compile() if compiled else lowered).as_text()
    n = text.count("tpu_custom_call")
    where = "compiled" if compiled else "lowered"
    if n == 0:
        return fail(f"{name}: no tpu_custom_call in the {where} program (the "
                    "kernel took a non-Pallas path)")
    print(f"  {name}: {n} tpu_custom_call in the {where} program")


#: checks that failed; a phase goes on after a failed check, so that one run
#: shows every mismatch, and fails at its end
FAILED: list = []


def fail(msg):
    print(f"  FAILED: {msg}", flush=True)
    FAILED.append(msg)


def compare(np, name, got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return fail(f"{name}: shape {got.shape} != {want.shape}")
    rtol, atol = tol
    err = float(np.max(np.abs(got.astype(np.float64)
                              - want.astype(np.float64)), initial=0.0))
    ok = (np.array_equal(got, want) if rtol == atol == 0.0
          else np.allclose(got, want, rtol=rtol, atol=atol))
    print(f"  {name}: max |got - ref| {err} (rtol {rtol}, atol {atol})")
    if not ok:
        fail(f"{name}: outside (rtol {rtol}, atol {atol}) of its reference, "
             f"max error {err}")


def stage_outputs(stage, ins):
    out = stage.kernel.executor(*ins, *stage.consts, **stage.params)
    return out if isinstance(out, tuple) else (out,)


def check_device(jax):
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    from repro.kernels.common import use_interpret
    if use_interpret():
        raise SystemExit("chip_smoke: Pallas kernels would run in "
                         "interpret mode on this TPU")
    print(f"  {dev.platform} {dev.device_kind} x{len(devices)}")
    return dev, len(devices)


def paper_path(jax, jnp, np):
    from repro.apps.tinybio import TINYBIO_WORKLOAD, tinybio_stages
    from repro.core import APU, EGPU_16T, Program, Stage
    from repro.serve import QueueWorker, Server

    rng = np.random.default_rng(SEED)
    apu = APU(EGPU_16T)
    gemm = Program.build(EGPU_16T).create_kernel("gemm")
    for side in GEMM_SIDES:
        a = rng.integers(-64, 64, (side, side), dtype=np.int32)
        b = rng.integers(-64, 64, (side, side), dtype=np.int32)
        stage = Stage(gemm, counts_params={"m": side, "n": side, "k": side})
        (out,), _ = apu.offload([stage], (jnp.asarray(a), jnp.asarray(b)))
        compare(np, f"gemm int32 {side}x{side} vs numpy", out.data, a @ b,
                (0.0, 0.0))
        require_mosaic(jax, f"gemm int32 {side}x{side}", gemm.executor, a, b)

    n = TINYBIO_WORKLOAD["n"]
    stages, (signal,) = tinybio_stages(EGPU_16T, SEED)
    ref_stages, _ = tinybio_stages(EGPU_16T, SEED, use_pallas=False)
    server = Server(stages, workers=(QueueWorker(EGPU_16T, name="tinybio"),),
                    bucket_sizes=(n,), max_batch=1)
    rid = server.submit(signal)
    server.flush()
    (served,) = server.result(rid)
    if server.report().n_shed:
        raise AssertionError("TinyBio: the server shed the request")
    # each Pallas stage runs on its reference's input, so every stage is
    # judged alone; the references run at full float32 matmul precision
    ins = (signal,)
    for stage, ref in zip(stages, ref_stages):
        got = stage_outputs(stage, ins)
        with jax.default_matmul_precision("highest"):
            want = stage_outputs(ref, ins)
        name = stage.kernel.name
        for i, (g, w) in enumerate(zip(got, want)):
            compare(np, f"tinybio {name}[{i}] vs ref.py", g, w,
                    STAGE_TOL[name])
        require_mosaic(jax, f"tinybio {name}",
                       lambda *x, st=stage: stage_outputs(st, x), *ins)
        ins = want
    compare(np, "tinybio served pipeline vs ref.py chain", served, ins[0],
            PIPELINE_TOL)


def bf16_spacing(np, x):
    """Distance from |x| to the next bfloat16 (8 significant bits)."""
    return float(2.0 ** (np.floor(np.log2(max(abs(x), 2.0 ** -126))) - 7))


def lm_serving(jax, jnp, np, clock):
    from repro.configs import ARCHS
    from repro.models import init_params, model_spec
    from repro.models.transformer import prefill
    from repro.serve import DecodeEngine, Server
    from repro.train.serve import (greedy_generate, make_decode_step,
                                   make_prefill_step)

    cfg = ARCHS[LM_ARCH]
    with clock.phase("lm.init"):
        params = init_params(model_spec(cfg), jax.random.PRNGKey(SEED),
                             dtype=jnp.dtype(cfg.dtype))
        jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"  {cfg.name}: {n_bytes} bytes of {cfg.dtype} params")
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, LM_PROMPT_LENS[i % 2],
                            dtype=np.int32) for i in range(LM_REQUESTS)]

    engine = DecodeEngine(cfg, params, num_slots=LM_SLOTS,
                          max_len=LM_MAX_LEN)
    server = Server((), workers=(), engine=engine)

    def serve_round():
        rids = [server.submit_decode(p, max_new=LM_MAX_NEW) for p in prompts]
        streamed = list(server.stream(rids[0]))
        server.flush()
        outs = [np.asarray(server.result(r)[0]) for r in rids]
        if streamed != [int(t) for t in outs[0]]:
            raise AssertionError("stream() and result() disagree")
        return outs

    with clock.phase("lm.serve.cold"):
        outs = serve_round()
    with clock.phase("lm.serve.warm"):
        warm = serve_round()
    if any(not np.array_equal(a, b) for a, b in zip(outs, warm)):
        fail("the warm round served different tokens")
    report = server.report()
    if report.n_shed:
        fail(f"{report.n_shed} requests were shed")
    if engine.cache.misses != LM_GRAPHS:
        fail(f"engine captured {engine.cache.misses} graphs, expected "
             f"{LM_GRAPHS}")
    print(f"  {2 * LM_REQUESTS} requests, {report.n_shed} shed, engine graph "
          f"cache {engine.cache.stats()}")
    for i, toks in enumerate(outs):
        print(f"  request {i} (prompt {len(prompts[i])}): "
              f"{' '.join(str(int(t)) for t in toks)}")
    for s in LM_PROMPT_LENS:
        require_mosaic(jax, f"prefill S={s} (flash attention)",
                       make_prefill_step(cfg, LM_MAX_LEN), params,
                       {"tokens": jnp.zeros((1, s), jnp.int32)},
                       compiled=False)

    with clock.phase("lm.reference"):
        refs = [np.asarray(greedy_generate(
            params, cfg, jnp.asarray(p)[None], max_new=LM_MAX_NEW,
            max_len=LM_MAX_LEN))[0] for p in prompts]
    step_fn = jax.jit(make_decode_step(cfg))

    def reference_logits(prompt, tokens, step):
        """The logits greedy_generate chose token ``step`` from."""
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                                cfg, LM_MAX_LEN)
        for i in range(step):
            _, logits, cache = step_fn(params, cache,
                                       jnp.asarray(tokens[i:i + 1]),
                                       jnp.int32(len(prompt) + i))
        return np.asarray(logits[0], np.float64)

    identical = 0
    for i, (got, want) in enumerate(zip(outs, refs)):
        diff = np.nonzero(got != want)[0]
        if diff.size == 0:
            identical += 1
            continue
        k = int(diff[0])
        logits = reference_logits(prompts[i], want, k)
        top2 = np.sort(logits)[-2:]
        margin = logits[want[k]] - logits[got[k]]
        tol = LOGIT_TIE_ULPS * bf16_spacing(np, logits[want[k]])
        print(f"  request {i}: diverges from greedy_generate at token {k}: "
              f"reference {int(want[k])}, engine {int(got[k])}; reference "
              f"top-2 logit gap {top2[1] - top2[0]}, reference choice beats "
              f"the engine's by {margin} (tie tolerance {tol})")
        if margin > tol:
            fail(f"request {i}: engine token {int(got[k])} at step {k} is "
                 f"{margin} below the reference's choice (> {tol})")
    print(f"  {identical}/{LM_REQUESTS} requests bit-identical to "
          "greedy_generate")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          "(chip number)")


def sharded_lane(jax, jnp, np):
    from repro.apps.tinybio import TINYBIO_WORKLOAD, synth_signal, \
        tinybio_stages
    from repro.core import EGPU_16T, Program, Stage
    from repro.serve import QueueWorker, Server, ShardedWorker, data_mesh

    def serve(worker, stages, requests, bucket):
        server = Server(stages, workers=(worker,), bucket_sizes=(bucket,),
                        max_batch=len(requests))
        rids = [server.submit(x) for x in requests]
        server.flush()
        report = server.report()
        if report.n_shed:
            raise AssertionError(f"{worker.name}: {report.n_shed} shed")
        return [np.asarray(server.result(r)[0]) for r in rids], report

    n = TINYBIO_WORKLOAD["n"]
    bio, _ = tinybio_stages(EGPU_16T, SEED)
    signals = [jnp.asarray(synth_signal(n, seed=SEED + i))
               for i in range(SHARDED_CHIPS)]
    rng = np.random.default_rng(SEED)
    side = GEMM_SIDES[0]
    b = rng.integers(-64, 64, (side, side), dtype=np.int32)
    gemm = [Stage(Program.build(EGPU_16T).create_kernel("gemm"),
                  consts=(jnp.asarray(b),), n_inputs=1,
                  counts_params={"m": side, "n": side, "k": side})]
    mats = [rng.integers(-64, 64, (side, side), dtype=np.int32)
            for _ in range(SHARDED_CHIPS)]
    buckets = (("tinybio", bio, signals, n),
               ("gemm", gemm, [jnp.asarray(a) for a in mats], side))
    served = {}
    for name, stages, requests, bucket in buckets:
        single, _ = serve(QueueWorker(EGPU_16T, name="single"), stages,
                          requests, bucket)
        lane = ShardedWorker(EGPU_16T, data_mesh(SHARDED_CHIPS), name="mesh")
        sharded, report = serve(lane, stages, requests, bucket)
        (qs,) = report.queues
        if qs.shards != SHARDED_CHIPS:
            raise AssertionError(f"{name}: the lane spans {qs.shards} "
                                 f"devices, expected {SHARDED_CHIPS}")
        for i, (s, m) in enumerate(zip(single, sharded)):
            compare(np, f"{name} request {i}: {SHARDED_CHIPS}-chip lane vs "
                    "single-device lane", m, s, (0.0, 0.0))
        print(f"  {name}: {len(requests)} requests over {qs.shards} chips, "
              f"mesh utilization {dict(qs.mesh_utilization)}")
        served[name] = sharded
    for i, (a, got) in enumerate(zip(mats, served["gemm"])):
        compare(np, f"gemm request {i} vs numpy", got, a @ b, (0.0, 0.0))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1, help="4: run only the sharded serving lane")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache

    print(f"  compilation cache: {enable_compile_cache()}")
    clock = PhaseClock(jax)
    with clock.phase("device"):
        dev, count = check_device(jax)
    if count < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX found {count}")
    if args.chips == SHARDED_CHIPS:
        run_phase(clock, "sharded", sharded_lane, jax, jnp, np)
    else:
        run_phase(clock, "paper", paper_path, jax, jnp, np)
        run_phase(clock, "lm", lm_serving, jax, jnp, np, clock)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} checks failed:", file=sys.stderr)
        for msg in FAILED:
            print(f"  {msg}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
