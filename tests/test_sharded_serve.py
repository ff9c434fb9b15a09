"""Sharded serving (ISSUE 5): ShardedWorker mesh lanes under the dispatcher.

Three coverage tiers, because the main test process must keep its real
device layout (see conftest):

* single-device tests — a 1-device mesh is a degenerate but fully wired
  ShardedWorker: placement-keyed cache isolation, divisibility fallback
  and report plumbing all run on any host;
* 2-device in-process tests — skipped unless the interpreter already has
  >= 2 devices (the CI matrix leg with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` exercises them
  on hosted runners);
* a SUBPROCESS test (always runs) — the acceptance pin: the paper's
  TinyBio bucket served through a ShardedWorker on a 2-device mesh is
  bit-identical to the plain QueueWorker path, and a shared GraphCache
  shows zero key collisions between the two.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EGPU_16T, Kernel, Stage
from repro.kernels.gemm.ref import counts as gemm_counts
from repro.kernels.gemm.ref import gemm_ref
from repro.launch.mesh import auto_mesh
from repro.serve import (GraphCache, QueueWorker, Server, ShardedWorker,
                         data_mesh, shard_breakdown)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (CI matrix leg forces 2 host devices)")


def _mm_stages(d=8, seed=0, n=2):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)

    def mlp(x, w):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    kern = Kernel("mlp", executor=mlp,
                  counts=lambda **kw: gemm_counts(m=d, n=d, k=d))
    return [Stage(kern, consts=(w,), n_inputs=1) for _ in range(n)]


def _requests(n, d=8, seed=5):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((int(rng.integers(3, d + 1)), d)),
                        jnp.float32) for _ in range(n)]


# ---------------------------------------------------------------------------
# Single-device coverage (1-device mesh: degenerate but fully wired)
# ---------------------------------------------------------------------------
def test_one_device_mesh_serves_and_reports():
    stages = _mm_stages()
    worker = ShardedWorker(EGPU_16T, data_mesh(1), name="mesh1")
    srv = Server(stages, workers=(worker,), bucket_sizes=(8,), max_batch=2)
    xs = _requests(4)
    rids = [srv.submit(x) for x in xs]
    srv.flush()
    for rid, x in zip(rids, xs):
        (out,) = srv.result(rid)
        assert out.shape == x.shape
    rep = srv.report()
    (qs,) = rep.queues
    assert qs.shards == 1
    assert qs.mesh_axes == (("data", 1),)
    # a 1-device axis is always fully utilized (factor 1 of size 1)
    assert dict(qs.mesh_utilization) == {"data": 1.0}
    assert rep.mesh_utilization == {"data": 1.0}
    assert "mesh data=1" in rep.summary()


def test_sharded_and_plain_cache_entries_never_collide():
    """Same pipeline, same bucket, shared cache: the sharded worker's
    placement must key a SEPARATE entry (zero collisions both ways)."""
    stages = _mm_stages()
    cache = GraphCache(capacity=8)
    plain = QueueWorker(EGPU_16T, name="plain")
    sharded = ShardedWorker(EGPU_16T, data_mesh(1), name="mesh")
    for srv_workers in ((plain,), (sharded,)):
        srv = Server(stages, workers=srv_workers, bucket_sizes=(8,),
                     max_batch=2)
        srv.cache = cache
        for x in _requests(2):
            srv.submit(x)
        srv.flush()
    assert cache.misses == 2 and len(cache) == 2
    # warm replays hit their own entries
    for srv_workers in ((plain,), (sharded,)):
        srv = Server(stages, workers=srv_workers, bucket_sizes=(8,),
                     max_batch=2)
        srv.cache = cache
        for x in _requests(2):
            srv.submit(x)
        srv.flush()
    assert cache.misses == 2 and cache.hits >= 2


def test_placement_distinguishes_mesh_and_rules():
    w1 = ShardedWorker(EGPU_16T, data_mesh(1), name="a")
    w2 = ShardedWorker(EGPU_16T, data_mesh(1), name="b")
    assert w1.apu.placement == w2.apu.placement    # same mesh layout: share
    from repro.distributed.sharding import SERVE_RULES
    w3 = ShardedWorker(EGPU_16T, data_mesh(1), name="c",
                       rules=SERVE_RULES.with_seq_sharding(True))
    assert w3.apu.placement != w1.apu.placement
    assert QueueWorker(EGPU_16T, name="d").apu.placement is None


def test_shard_breakdown_scales_only_work_phases():
    from repro.core.machine import PhaseBreakdown
    pb = PhaseBreakdown(startup=100.0, scheduling=50.0, transfer=40.0,
                        compute=200.0, freq_hz=1e6)
    sb = shard_breakdown(pb, 2)
    assert sb.startup == 100.0 and sb.scheduling == 50.0
    assert sb.transfer == 20.0 and sb.compute == 100.0
    assert shard_breakdown(pb, 1) is pb


def test_sharded_worker_rejects_bad_mesh():
    with pytest.raises(TypeError):
        ShardedWorker(EGPU_16T, mesh="not-a-mesh")
    with pytest.raises(ValueError):
        data_mesh(0)
    with pytest.raises(ValueError):
        data_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# >= 2 devices in-process (the CI 2-device matrix leg runs these)
# ---------------------------------------------------------------------------
@multi_device
def test_two_shard_results_bit_identical_and_modeled_scaled():
    stages = _mm_stages(n=3)
    xs = _requests(8)
    outs, modeled = {}, {}
    for key, worker in (("plain", QueueWorker(EGPU_16T, name="p")),
                        ("sharded", ShardedWorker(EGPU_16T, data_mesh(2),
                                                  name="s"))):
        srv = Server(stages, workers=(worker,), bucket_sizes=(8,),
                     max_batch=2)
        rids = [srv.submit(x) for x in xs]
        srv.flush()
        outs[key] = [np.asarray(srv.result(r)[0]) for r in rids]
        modeled[key] = srv.report().queues[0].modeled_s
    for a, b in zip(outs["plain"], outs["sharded"]):
        np.testing.assert_array_equal(a, b)
    # transfer+compute halve, startup+scheduling don't: strictly between
    assert modeled["sharded"] < modeled["plain"]
    assert modeled["sharded"] > modeled["plain"] / 2


@multi_device
def test_divisibility_fallback_replicates_odd_capacity():
    """max_batch=3 on a 2-shard data axis: 3 % 2 != 0, so the batch axis
    must fall back to replication (shards=1, full results, honest
    utilization < 1) instead of failing to lower."""
    stages = _mm_stages()
    worker = ShardedWorker(EGPU_16T, data_mesh(2), name="odd")
    srv = Server(stages, workers=(worker,), bucket_sizes=(8,), max_batch=3)
    xs = _requests(3)
    rids = [srv.submit(x) for x in xs]
    srv.flush()
    for rid, x in zip(rids, xs):
        (out,) = srv.result(rid)
        assert out.shape == x.shape
    (qs,) = srv.report().queues
    assert qs.shards == 2                    # the lane still spans 2 devices
    assert dict(qs.mesh_utilization)["data"] == pytest.approx(0.5)
    assert srv.report().mesh_utilization["data"] == pytest.approx(0.5)


@multi_device
def test_dispatcher_routes_mixed_plain_and_sharded_lanes():
    stages = _mm_stages()
    plain = QueueWorker(EGPU_16T, name="plain")
    sharded = ShardedWorker(EGPU_16T, data_mesh(2), name="mesh2")
    srv = Server(stages, workers=(plain, sharded), bucket_sizes=(8,),
                 max_batch=2, max_in_flight=2)
    for x in _requests(20):
        srv.submit(x)
    srv.flush()
    rep = srv.report()
    per = {q.name: q for q in rep.queues}
    assert per["plain"].batches + per["mesh2"].batches == 10
    # both lanes bootstrap; after that the sharded lane's lower modeled
    # seconds-per-request wins depth ties, attracting more traffic
    assert per["mesh2"].batches > per["plain"].batches
    assert per["plain"].batches >= 1
    assert rep.mesh_utilization == {"data": 1.0}


@multi_device
def test_const_axes_shard_model_parallel_stage_args():
    """A constant tagged with a divisible logical axis lands on 'model'."""
    d = 8
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)

    def mlp(x, w):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    stages = [Stage(Kernel("mlp", executor=mlp,
                           counts=lambda **kw: gemm_counts(m=d, n=d, k=d)),
                    consts=(w,), n_inputs=1)]
    mesh = auto_mesh((1, 2), ("data", "model"))
    worker = ShardedWorker(EGPU_16T, mesh, name="mp",
                           const_axes=((None, "mlp"),))
    srv = Server(stages, workers=(worker,), bucket_sizes=(8,), max_batch=2)
    xs = _requests(2)
    rids = [srv.submit(x) for x in xs]
    srv.flush()
    ref = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,), max_batch=2)
    rids_ref = [ref.submit(x) for x in xs]
    ref.flush()
    for rs, rr in zip(rids, rids_ref):
        np.testing.assert_array_equal(np.asarray(srv.result(rs)[0]),
                                      np.asarray(ref.result(rr)[0]))
    # the model-parallel const registers on the "model" axis: utilization
    # distinguishes a healthy MP lane (100%) from a replication fallback
    (qs,) = srv.report().queues
    assert dict(qs.mesh_utilization)["model"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Acceptance pin (always runs): TinyBio, 2-device mesh, subprocess
# ---------------------------------------------------------------------------
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.tinybio import synth_signal, tinybio_stages
from repro.core import EGPU_16T
from repro.serve import (GraphCache, QueueWorker, Server, ShardedWorker,
                         data_mesh)

assert len(jax.devices()) == 2, jax.devices()
stages, _ = tinybio_stages(EGPU_16T)
n = 65_536
sigs = [jnp.asarray(synth_signal(n, seed=s)) for s in (3, 4)]
cache = GraphCache(capacity=8)

def serve(worker):
    srv = Server(stages, workers=(worker,), bucket_sizes=(n,), max_batch=2)
    srv.cache = cache
    rids = [srv.submit(s) for s in sigs]
    srv.flush()
    return [srv.result(r) for r in rids], srv

plain, _ = serve(QueueWorker(EGPU_16T, name="single"))
sharded, srv = serve(ShardedWorker(EGPU_16T, data_mesh(2), name="mesh"))

identical = all(
    len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))
    for a, b in zip(plain, sharded))
host = all(isinstance(o, np.ndarray) for r in plain + sharded for o in r)
rep = srv.report()
qs = rep.queues[0]
print(json.dumps({
    "identical": identical,
    "host": host,
    "fetches": [rep.n_result_fetches, rep.n_batches],
    "cache": cache.stats(),
    "shards": qs.shards,
    "util": dict(qs.mesh_utilization),
}))
"""


def test_tinybio_sharded_bit_identical_subprocess():
    result = _run_two_device_script(SCRIPT)
    # bit-identical through the sharded lane, fetched to the host once a
    # ticket (the device_get gathers the data-sharded output)
    assert result["identical"]
    assert result["host"]
    assert result["fetches"] == [1, 1]
    # one entry per worker in the SHARED cache: zero key collisions (a
    # collision would read as 1 miss + 1 hit), nothing evicted
    assert result["cache"]["misses"] == 2
    assert result["cache"]["hits"] == 0
    assert result["cache"]["evictions"] == 0
    # the full TinyBio bucket (batch 2 over data=2) genuinely sharded
    assert result["shards"] == 2
    assert result["util"] == {"data": 1.0}


MM_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EGPU_16T, Kernel, Stage
from repro.kernels.gemm.ref import gemm_ref
from repro.serve import QueueWorker, Server, ShardedWorker, data_mesh

assert len(jax.devices()) == 2, jax.devices()
rng = np.random.default_rng(0)
w = jnp.asarray(rng.standard_normal((8, 8)) * 0.2, jnp.float32)
stages = [Stage(Kernel("mlp", executor=lambda x, w: jnp.maximum(
    gemm_ref(x, w), 0.0)), consts=(w,), n_inputs=1)]
xs = [jnp.asarray(rng.standard_normal((int(rng.integers(1, 9)), 8)),
                  jnp.float32) for _ in range(7)]

def serve(worker):
    srv = Server(stages, workers=(worker,), bucket_sizes=(8,), max_batch=2)
    out = []
    for group in (xs[:5], xs[5:]):       # 2 full + 1 partial, then 1 full
        rids = [srv.submit(x) for x in group]
        srv.flush()
        out += [srv.result(r) for r in rids]
    rep = srv.report()
    return out, [rep.n_result_fetches, rep.n_batches]

plain, plain_fetches = serve(QueueWorker(EGPU_16T, name="single"))
sharded, sharded_fetches = serve(
    ShardedWorker(EGPU_16T, data_mesh(2), name="mesh"))
print(json.dumps({
    "host": all(isinstance(o, np.ndarray)
                for r in plain + sharded for o in r),
    "shapes": [list(r[0].shape) == list(x.shape)
               for r, x in zip(sharded, xs)],
    "identical": all(a[0].tobytes() == b[0].tobytes()
                     for a, b in zip(plain, sharded)),
    "fetches": [plain_fetches, sharded_fetches],
}))
"""


def _run_two_device_script(script):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # the script sets its own
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_results_fetched_to_host_subprocess():
    """On a 2-device data mesh, full and partial batches come back through
    one host fetch a ticket, cropped to each request's rows, and equal a
    plain QueueWorker's results bit for bit."""
    result = _run_two_device_script(MM_SCRIPT)
    assert result["host"]
    assert all(result["shapes"])
    assert result["identical"]
    assert result["fetches"] == [[4, 4], [4, 4]]


# ---------------------------------------------------------------------------
# Fault injection through a sharded lane (ISSUE 6)
# ---------------------------------------------------------------------------
def test_sharded_lane_blackout_reroutes_bit_identical():
    """The fault gate fires inside ShardedWorker._do_launch too: a
    blacked-out mesh lane reroutes its micro-batches to the plain sibling,
    results stay bit-identical, and the lane serves again after the
    window."""
    from repro.serve import Blackout, FaultPlan, env_seed
    stages = _mm_stages()
    plan = FaultPlan(seed=env_seed(11),
                     blackouts=(Blackout("mesh", start=0, length=2),))
    mesh_lane = ShardedWorker(EGPU_16T, data_mesh(1), name="mesh",
                              fault_plan=plan)
    plain_lane = QueueWorker(EGPU_16T, name="plain", fault_plan=plan)
    srv = Server(stages, workers=(mesh_lane, plain_lane), bucket_sizes=(8,),
                 max_batch=2, breaker_threshold=2, breaker_cooldown=1)
    xs = _requests(12)
    rids = [srv.submit(x) for x in xs]
    srv.flush()
    rep = srv.report()
    assert rep.n_shed == 0 and rep.n_dispatch_failures == 0
    assert rep.n_retries >= 1
    per = {q.name: q for q in rep.queues}
    assert per["mesh"].launch_failures == 2
    assert per["mesh"].batches >= 1          # recovered after the window
    assert per["plain"].batches >= 1
    ref = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,), max_batch=2)
    rids_ref = [ref.submit(x) for x in xs]
    ref.flush()
    for rs, rr in zip(rids, rids_ref):
        np.testing.assert_array_equal(np.asarray(srv.result(rs)[0]),
                                      np.asarray(ref.result(rr)[0]))
