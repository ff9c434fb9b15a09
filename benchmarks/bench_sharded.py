"""Sharded serving: a 2-shard data-parallel lane vs the single-worker path.

ISSUE 5's tentpole claim is that a ``repro.serve.ShardedWorker`` spanning a
2-device data mesh serves a compute-bound bucket with ~2x the modeled
requests/s of a plain single-device ``QueueWorker`` (each mesh slice runs
half the micro-batch; startup + scheduling are still paid once per launch,
so the ratio lands below 2 exactly by the dispatch fraction).  Like the
multiqueue and transfer benches, the CI gate sits on the **deterministic
machine-model** ratio (>= 1.3x): wall-clock speedup from 2 fake host
devices depends entirely on how many cores the runner has left over after
XLA's intra-op parallelism, so it is reported but not gated (a 2-core dev
host measures ~1.1-1.2x; a wider host approaches the modeled ratio).

The bench also pins the tentpole's correctness claim: the paper's TinyBio
pipeline served through the sharded lane must be **bit-identical** to the
single-device graph path, with zero key collisions in a shared GraphCache.

Everything runs in a SUBPROCESS with ``--xla_force_host_platform_device_
count=2`` (the device count must be set before jax initializes, and the
parent bench process must keep whatever device layout it started with);
results are appended to ``BENCH_serve.json`` tagged ``bench=sharded``.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT_PATH = ROOT / "BENCH_serve.json"

SIZE = 64          # GeMM operand side (compute-bound on the machine model)
CHAIN = 6          # dependent stages per pipeline
BATCH = 8          # micro-batch capacity (divisible by the 2 data shards)
N_REQ = 64         # timed requests per path
GATE = 1.3


def _child() -> None:
    """Measure inside the 2-device subprocess; print one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps.tinybio import synth_signal, tinybio_stages
    from repro.core import EGPU_16T, Kernel, Stage
    from repro.kernels.gemm.ref import counts as gemm_counts
    from repro.kernels.gemm.ref import gemm_ref
    from repro.serve import (GraphCache, QueueWorker, Server, ShardedWorker,
                             data_mesh)

    assert len(jax.devices()) >= 2, jax.devices()
    mesh = data_mesh(2)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    # -- compute-bound GeMM chain: modeled + measured requests/s ------------
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((SIZE, SIZE)) * 0.05, jnp.float32)

    def mlp(x, w):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    kern = Kernel("mlp", executor=mlp,
                  counts=lambda **kw: gemm_counts(m=SIZE, n=SIZE, k=SIZE))
    stages = [Stage(kern, consts=(w,), n_inputs=1) for _ in range(CHAIN)]

    xs = [jnp.asarray(rng.standard_normal((SIZE, SIZE)), jnp.float32)
          for _ in range(N_REQ)]

    def serve_all(worker):
        srv = Server(stages, workers=(worker,), bucket_sizes=(SIZE,),
                     max_batch=BATCH, max_in_flight=2)
        x0 = jnp.zeros((SIZE, SIZE), jnp.float32)
        srv.warmup(x0)
        for x in xs[:BATCH]:             # prime: first launch jit-compiles
            srv.submit(x)
        srv.flush()
        t0 = time.perf_counter()
        rids = [srv.submit(x) for x in xs]
        srv.flush()
        wall = time.perf_counter() - t0
        outs = [np.asarray(srv.result(r)[0]) for r in rids]
        qs = srv.report().queues[0]
        assert srv.cache.misses == 1, srv.cache.stats()
        # modeled seconds for the timed traffic only (prime round excluded)
        modeled = qs.modeled_s * N_REQ / qs.requests
        return wall, modeled, outs

    log(f"[sharded] GeMM chain {CHAIN}x{SIZE}x{SIZE}, batch {BATCH}, "
        f"{N_REQ} requests per path")
    wall_1, modeled_1, outs_1 = serve_all(
        QueueWorker(EGPU_16T, name="single"))
    wall_2, modeled_2, outs_2 = serve_all(
        ShardedWorker(EGPU_16T, mesh, name="data2"))
    for a, b in zip(outs_1, outs_2):
        assert np.array_equal(a, b), "sharded GeMM chain diverged"

    modeled_speedup = modeled_1 / modeled_2
    measured_speedup = wall_1 / wall_2
    log(f"[sharded] modeled  {N_REQ / modeled_1:12,.0f} req/s single   "
        f"{N_REQ / modeled_2:12,.0f} req/s sharded   {modeled_speedup:.2f}x")
    log(f"[sharded] measured {N_REQ / wall_1:12,.0f} req/s single   "
        f"{N_REQ / wall_2:12,.0f} req/s sharded   {measured_speedup:.2f}x "
        "(not gated: wall clock on fake host devices is core-count-bound)")

    # -- TinyBio bit-identity through a shared cache ------------------------
    log("[sharded] TinyBio bucket: sharded vs single-device bit-identity")
    cache = GraphCache(capacity=8)
    bio_stages, _ = tinybio_stages(EGPU_16T)
    n = 65_536
    sigs = [jnp.asarray(synth_signal(n, seed=s)) for s in (3, 4)]

    def bio_results(worker):
        srv = Server(bio_stages, workers=(worker,), bucket_sizes=(n,),
                     max_batch=2)
        srv.cache = cache
        rids = [srv.submit(s) for s in sigs]
        srv.flush()
        return [tuple(np.asarray(o) for o in srv.result(r)) for r in rids]

    bio_1 = bio_results(QueueWorker(EGPU_16T, name="bio-single"))
    bio_2 = bio_results(ShardedWorker(EGPU_16T, mesh, name="bio-data2"))
    identical = all(
        len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))
        for a, b in zip(bio_1, bio_2))
    assert cache.misses == 2 and cache.evictions == 0, cache.stats()
    log(f"[sharded] TinyBio bit-identical: {identical}, cache "
        f"{cache.stats()['misses']} misses (zero collisions)")

    print(json.dumps({
        "bench": "sharded",
        "mesh": {"data": 2},
        "size": SIZE,
        "chain_len": CHAIN,
        "max_batch": BATCH,
        "n_requests": N_REQ,
        "shards": 2,
        "requests_per_s_modeled": {"single": N_REQ / modeled_1,
                                   "sharded": N_REQ / modeled_2},
        "requests_per_s_modeled_speedup": modeled_speedup,
        "requests_per_s_measured": {"single": N_REQ / wall_1,
                                    "sharded": N_REQ / wall_2},
        "requests_per_s_measured_speedup": measured_speedup,
        "tinybio_bit_identical": bool(identical),
        "tinybio_cache_stats": cache.stats(),
    }))


def run():
    print("=" * 76)
    print("Sharded serving: 2-shard data-parallel lane vs single worker")
    print(f"(chain of {CHAIN} dependent {SIZE}x{SIZE} GeMM stages, "
          f"micro-batch {BATCH}, subprocess with 2 forced host devices)")
    print("  the subprocess runs with JAX_PLATFORMS=cpu: it rehearses the "
          "sharding on two virtual CPU devices and never takes the "
          "accelerator this process may hold")
    print("=" * 76)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + ([env["PYTHONPATH"]]
                               if env.get("PYTHONPATH") else []))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_sharded", "--child"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1800)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess failed (rc {proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    result = json.loads(proc.stdout.strip().splitlines()[-1])

    ratio = result["requests_per_s_modeled_speedup"]
    print(f"  modeled  requests/s speedup {ratio:.2f}x (>= {GATE}x CI gate)")
    print(f"  measured requests/s speedup "
          f"{result['requests_per_s_measured_speedup']:.2f}x (reported, "
          "not gated)")
    print(f"  TinyBio sharded output bit-identical: "
          f"{result['tinybio_bit_identical']}")
    assert ratio >= GATE, (
        f"2-shard lane models only {ratio:.2f}x the single-worker "
        "requests/s — the data-parallel scaling (or its accounting) broke")
    assert result["tinybio_bit_identical"], \
        "sharded TinyBio output diverged from the single-device graph path"

    from .history import append_entry
    history = append_entry(OUT_PATH, result)
    print(f"  appended to {OUT_PATH.name} (run #{len(history)})")
    return result


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
