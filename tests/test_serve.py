"""repro.serve — graph cache, dynamic batching, multi-queue dispatch
(ISSUE 2).

Pins the subsystem's contracts: cache hit/miss/eviction and config
isolation, cached-launch numerical identity with fresh capture, batcher
padding at bucket boundaries, the warm-server zero-re-capture guarantee
with bit-identical batched results, event-lifecycle memory bounds, and
dispatcher backpressure.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (APU, EGPU_4T, EGPU_8T, EGPU_16T, CommandQueue,
                        Context, Device, Kernel, NDRange, Stage, WorkCounts)
from repro.kernels.gemm.ref import counts as gemm_counts
from repro.kernels.gemm.ref import gemm_ref
from repro.serve import (BucketBatcher, GraphCache, MultiQueueDispatcher,
                         QueueWorker, Server, batched_stages)

NDR = NDRange((8, 8), (4, 4))


def _mm_stages(d=8, seed=0, n=1):
    """n chained (x @ W -> relu) stages with a fixed weight."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)

    def mlp(x, w):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    kern = Kernel("mlp", executor=mlp,
                  counts=lambda **kw: gemm_counts(m=d, n=d, k=d))
    return [Stage(kern, consts=(w,), n_inputs=1) for _ in range(n)]


def _x(shape=(8, 8), seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# ---------------------------------------------------------------------------
# GraphCache
# ---------------------------------------------------------------------------
def test_cache_hit_miss_counters():
    cache = GraphCache(capacity=4)
    apu = APU(EGPU_16T, graph_cache=cache)
    stages = _mm_stages()
    x = _x()
    o1, _ = apu.offload(stages, (x,))
    assert (cache.hits, cache.misses) == (0, 1)
    o2, _ = apu.offload(stages, (x,))
    assert (cache.hits, cache.misses) == (1, 1)
    np.testing.assert_array_equal(np.asarray(o1[0].data),
                                  np.asarray(o2[0].data))
    # a different input SHAPE is a different entry
    apu.offload(stages, (_x((4, 8)),))
    assert (cache.hits, cache.misses) == (1, 2)


def test_cache_lru_eviction():
    cache = GraphCache(capacity=2)
    apu = APU(EGPU_16T, graph_cache=cache)
    stages = _mm_stages()
    xa, xb, xc = _x((2, 8)), _x((4, 8)), _x((6, 8))
    apu.offload(stages, (xa,))
    apu.offload(stages, (xb,))
    apu.offload(stages, (xa,))           # promote A to MRU
    apu.offload(stages, (xc,))           # evicts B (LRU)
    assert cache.evictions == 1 and len(cache) == 2
    apu.offload(stages, (xb,))           # B must re-capture (evicts A)
    assert cache.misses == 4 and cache.evictions == 2
    apu.offload(stages, (xc,))           # C still resident
    assert cache.hits == 2


def test_cache_distinct_configs_do_not_collide():
    cache = GraphCache(capacity=8)
    stages = _mm_stages()
    x = _x()
    outs = {}
    for cfg in (EGPU_4T, EGPU_8T, EGPU_16T):
        apu = APU(cfg, graph_cache=cache)
        (o,), rep = apu.offload(stages, (x,))
        outs[cfg.name] = np.asarray(o.data)
        # each config modeled with its own machine numbers
        assert rep.stages[0].egpu is not None
    assert cache.misses == 3 and cache.hits == 0
    # same pipeline again on each config: all hits now
    for cfg in (EGPU_4T, EGPU_8T, EGPU_16T):
        APU(cfg, graph_cache=cache).offload(stages, (x,))
    assert cache.hits == 3
    for name, o in outs.items():         # functional results config-invariant
        np.testing.assert_array_equal(o, outs[EGPU_16T.name])


def test_cache_distinct_consts_do_not_collide():
    """Same kernel names, different baked weights => different entries (a
    false hit would serve the wrong model)."""
    cache = GraphCache(capacity=8)
    apu = APU(EGPU_16T, graph_cache=cache)
    x = _x()
    (o1,), _ = apu.offload(_mm_stages(seed=0), (x,))
    (o2,), _ = apu.offload(_mm_stages(seed=7), (x,))
    assert cache.misses == 2
    assert not np.array_equal(np.asarray(o1.data), np.asarray(o2.data))


def test_cache_distinct_closures_do_not_collide():
    """Two lambdas born at the same source line capturing different values
    must get different entries — a false hit replays the wrong capture."""
    cache = GraphCache(capacity=8)
    apu = APU(EGPU_16T, graph_cache=cache)
    x = jnp.ones((4,), jnp.float32)

    def scale_stage(k):
        return [Stage(Kernel("scale", executor=lambda a: a * k))]

    (o2,), _ = apu.offload(scale_stage(2.0), (x,))
    (o3,), _ = apu.offload(scale_stage(3.0), (x,))
    assert cache.misses == 2
    np.testing.assert_array_equal(np.asarray(o2.data), 2.0 * np.ones(4))
    np.testing.assert_array_equal(np.asarray(o3.data), 3.0 * np.ones(4))
    # identical capture value => genuine hit
    apu.offload(scale_stage(2.0), (x,))
    assert cache.hits == 1


def test_cache_distinct_inline_literals_do_not_collide():
    """Executors differing only in an inline constant share co_code — the
    signature must still tell them apart (co_consts hashed)."""
    cache = GraphCache(capacity=8)
    apu = APU(EGPU_16T, graph_cache=cache)
    x = jnp.ones((4,), jnp.float32)
    (o2,), _ = apu.offload([Stage(Kernel("s", executor=lambda a: a * 2.0))],
                           (x,))
    (o3,), _ = apu.offload([Stage(Kernel("s", executor=lambda a: a * 3.0))],
                           (x,))
    assert cache.misses == 2
    np.testing.assert_array_equal(np.asarray(o2.data), 2.0 * np.ones(4))
    np.testing.assert_array_equal(np.asarray(o3.data), 3.0 * np.ones(4))


def test_cache_large_arrays_in_containers_do_not_collide():
    """A closure capturing a LIST of large arrays must sign element-wise —
    repr truncates big arrays to '...', which would collide."""
    cache = GraphCache(capacity=8)
    apu = APU(EGPU_16T, graph_cache=cache)
    x = jnp.ones((4,), jnp.float32)
    w1 = np.zeros(10_000, np.float32)
    w2 = w1.copy()
    w2[5_000] = 1.0                      # differs only mid-array

    def stage_for(ws):
        return [Stage(Kernel("pick", executor=lambda a: a * ws[0][5_000]))]

    (o1,), _ = apu.offload(stage_for([jnp.asarray(w1)]), (x,))
    (o2,), _ = apu.offload(stage_for([jnp.asarray(w2)]), (x,))
    assert cache.misses == 2             # no false hit
    np.testing.assert_array_equal(np.asarray(o1.data), np.zeros(4))
    np.testing.assert_array_equal(np.asarray(o2.data), np.ones(4))


def test_batch_dim_padding_uses_fill_value():
    b = BucketBatcher((4,), max_batch=3, fill=1.0)
    b.submit(jnp.full((4,), 2.0, jnp.float32))
    (mb,) = b.drain()
    # dead capacity rows use the configured fill, not zeros (kernels like
    # 1/x rely on it to stay finite)
    np.testing.assert_array_equal(np.asarray(mb.inputs[0][1:]),
                                  np.ones((2, 4), np.float32))


def test_cached_offload_reuses_pipeline_report():
    cache = GraphCache(capacity=4)
    apu = APU(EGPU_16T, graph_cache=cache)
    stages = _mm_stages()
    _, r1 = apu.offload(stages, (_x(),))
    _, r2 = apu.offload(stages, (_x(seed=5),))
    assert r2 is r1                      # launch-invariant, memoized


def test_cache_signature_memo_reused_for_same_stage_objects():
    cache = GraphCache(capacity=8)
    apu = APU(EGPU_16T, graph_cache=cache)
    stages = _mm_stages()
    x = _x()
    apu.offload(stages, (x,))
    apu.offload(stages, (x,))
    assert len(cache._sig_memo) == 1     # same Stage list: hashed once
    assert (cache.hits, cache.misses) == (1, 1)


def test_cached_launch_identical_to_fresh_capture():
    cache = GraphCache(capacity=4)
    cached_apu = APU(EGPU_16T, graph_cache=cache)
    fresh_apu = APU(EGPU_16T)            # no cache: re-captures every call
    stages = _mm_stages(n=3)
    for seed in (1, 2, 3):
        x = _x(seed=seed)
        (oc,), rep_c = cached_apu.offload(stages, (x,))
        (of,), rep_f = fresh_apu.offload(stages, (x,))
        np.testing.assert_array_equal(np.asarray(oc.data),
                                      np.asarray(of.data))
        # machine-model accounting identical through the cached path
        assert rep_c.overall_speedup == rep_f.overall_speedup
        assert rep_c.egpu_fused.total_s == rep_f.egpu_fused.total_s
    assert cache.misses == 1 and cache.hits == 2


# ---------------------------------------------------------------------------
# BucketBatcher
# ---------------------------------------------------------------------------
def test_bucket_selection_and_boundaries():
    b = BucketBatcher((8, 16), max_batch=2)
    assert b.bucket_size_for(1) == 8
    assert b.bucket_size_for(8) == 8     # exactly on the boundary: no bump
    assert b.bucket_size_for(9) == 16
    assert b.bucket_size_for(16) == 16
    with pytest.raises(ValueError):
        b.bucket_size_for(17)


def test_batcher_pads_and_crops_at_bucket_boundary():
    b = BucketBatcher((8,), max_batch=2)
    r1 = b.submit(jnp.arange(5, dtype=jnp.float32))     # padded 5 -> 8
    r2 = b.submit(jnp.arange(8, dtype=jnp.float32))     # exact fit: no pad
    (mb,) = b.pop_full()
    assert mb.inputs[0].shape == (2, 8)
    np.testing.assert_array_equal(
        np.asarray(mb.inputs[0][0]), [0, 1, 2, 3, 4, 0, 0, 0])
    np.testing.assert_array_equal(
        np.asarray(mb.inputs[0][1]), np.arange(8, dtype=np.float32))
    # crop returns each request's true extent
    outs = mb.crop([mb.inputs[0] * 2])
    assert outs[0][0].shape == (5,) and outs[1][0].shape == (8,)
    np.testing.assert_array_equal(np.asarray(outs[0][0]), [0, 2, 4, 6, 8])


def test_batcher_partial_batch_padded_to_capacity():
    b = BucketBatcher((4,), max_batch=3)
    b.submit(jnp.ones(4, jnp.float32))
    assert b.pop_full() == [] and b.n_pending == 1
    (mb,) = b.drain()
    assert mb.inputs[0].shape == (3, 4)  # batch dim padded to capacity
    assert mb.n_requests == 1 and b.n_pending == 0
    np.testing.assert_array_equal(np.asarray(mb.inputs[0][1]), np.zeros(4))


def test_batcher_pad_axis_1_crops_columns():
    """pad_axis=1: padding and cropping act on columns, not rows."""
    b = BucketBatcher((8,), max_batch=1, pad_axis=1)
    r = b.submit(jnp.ones((3, 5), jnp.float32))
    assert r.lengths == (5,)
    (mb,) = b.drain()
    assert mb.inputs[0].shape == (1, 3, 8)      # (batch, rows, padded cols)
    np.testing.assert_array_equal(np.asarray(mb.inputs[0][0, :, 5:]),
                                  np.zeros((3, 3)))
    ((out,),) = [mb.crop([mb.inputs[0] * 2])[0]]
    assert out.shape == (3, 5)                  # columns cropped back
    np.testing.assert_array_equal(np.asarray(out), 2 * np.ones((3, 5)))


def test_crop_outputs_false_returns_padded_rows():
    """Pipelines whose outputs have fixed dims equal to a bucket size must
    be able to opt out of the shape-match crop heuristic."""
    b = BucketBatcher((8,), max_batch=1, crop_outputs=False)
    r = b.submit(jnp.arange(5, dtype=jnp.float32))
    (mb,) = b.drain()
    (row,) = mb.crop([mb.inputs[0] * 2])[0]
    assert row.shape == (8,)             # padded extent kept
    assert r.lengths == (5,)             # caller slices with this


def _counting_device_get(monkeypatch):
    """Count every ``jax.device_get`` from here on; returns the counter."""
    calls = [0]
    real = jax.device_get

    def counted(x):
        calls[0] += 1
        return real(x)

    monkeypatch.setattr(jax, "device_get", counted)
    return calls


# (batcher kwargs, per-request array shapes, outputs from the batched
#  inputs, expected per-request output shapes)
CROP_CASES = {
    "full_pad0": (dict(bucket_sizes=(8,), max_batch=2),
                  [[(5,)], [(8,)]], lambda x: [x[0] * 2],
                  [[(5,)], [(8,)]]),
    "partial_pad0": (dict(bucket_sizes=(8,), max_batch=3),
                     [[(5,)]], lambda x: [x[0] * 2], [[(5,)]]),
    "full_pad1": (dict(bucket_sizes=(8,), max_batch=2, pad_axis=1),
                  [[(3, 5)], [(3, 8)]], lambda x: [x[0] * 2],
                  [[(3, 5)], [(3, 8)]]),
    "partial_pad1": (dict(bucket_sizes=(8,), max_batch=3, pad_axis=1),
                     [[(3, 5)], [(3, 2)]], lambda x: [x[0] - 1],
                     [[(3, 5)], [(3, 2)]]),
    "per_output_lengths": (dict(bucket_sizes=(8,), max_batch=2),
                           [[(5,), (7,)]], lambda x: [x[0] * 2, x[1] * 3],
                           [[(5,), (7,)]]),
    "crop_outputs_false": (dict(bucket_sizes=(8,), max_batch=2,
                                crop_outputs=False),
                           [[(5,)], [(3,)]], lambda x: [x[0] * 2],
                           [[(8,)], [(8,)]]),
    "two_outputs_one_reduced": (dict(bucket_sizes=(8, 16), max_batch=3),
                                [[(5,)], [(6,)]],
                                lambda x: [x[0] * 2, x[0].sum(axis=-1)],
                                [[(5,), ()], [(6,), ()]]),
}


@pytest.mark.parametrize("case", sorted(CROP_CASES))
def test_crop_fetches_host_rows(case, monkeypatch):
    """``crop`` fetches the launch's outputs in ONE ``jax.device_get`` and
    returns each request's true extent as host numpy arrays, bit-identical
    to the device rows they were cut from (full and partial batches, both
    pad axes, per-output lengths, ``crop_outputs=False``, two outputs)."""
    kwargs, shapes, outputs_of, expected = CROP_CASES[case]
    rng = np.random.default_rng(len(case))
    b = BucketBatcher(**kwargs)
    for arrays in shapes:
        b.submit(*(jnp.asarray(rng.standard_normal(s), jnp.float32)
                   for s in arrays))
    (mb,) = b.drain()
    outs = outputs_of(mb.inputs)
    device_rows = [np.asarray(o) for o in outs]
    calls = _counting_device_get(monkeypatch)
    got = mb.crop(outs)
    assert calls[0] == 1
    assert len(got) == len(shapes)
    for i, (row, want) in enumerate(zip(got, expected)):
        assert len(row) == len(want)
        for j, (arr, shape) in enumerate(zip(row, want)):
            assert isinstance(arr, np.ndarray)
            assert arr.shape == shape
            ref = device_rows[j][i][tuple(slice(0, n) for n in shape)]
            assert arr.dtype == ref.dtype
            assert arr.tobytes() == ref.tobytes()


def test_server_fetches_each_ticket_once(monkeypatch):
    """A Server run of full and partial batches makes exactly one
    ``jax.device_get`` per retired ticket, counted by
    ``repro_serve_result_fetches_total`` beside
    ``repro_serve_batches_total``; results are host arrays equal to the
    per-request eager offload."""
    stages = _mm_stages(n=2)
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=2, max_in_flight=2)
    srv.warmup(jnp.zeros((1, 8), jnp.float32))
    calls = _counting_device_get(monkeypatch)
    rng = np.random.default_rng(21)
    rids = []
    for n_submit in (5, 2, 3):           # full batches and partial ones
        for _ in range(n_submit):
            x = jnp.asarray(rng.standard_normal(
                (int(rng.integers(1, 9)), 8)), jnp.float32)
            rids.append((srv.submit(x), x))
        srv.flush()
    rep = srv.report()
    assert rep.n_requests == 10
    assert rep.n_batches == 6            # 4 full, 2 partial
    assert rep.n_result_fetches == rep.n_batches == calls[0]
    # one (2, 8, 8) float32 output a ticket, padding rows included
    assert rep.result_fetch_bytes == rep.n_batches * 2 * 8 * 8 * 4
    registry = srv.publish_metrics()
    fetches = registry.get("repro_serve_result_fetches_total").value()
    batches = registry.get("repro_serve_batches_total").value()
    assert fetches == batches == rep.n_batches
    assert (registry.get("repro_serve_result_fetch_bytes_total").value()
            == rep.result_fetch_bytes)
    apu = APU(EGPU_16T)
    for rid, x in rids:
        (got,) = srv.result(rid)
        assert isinstance(got, np.ndarray) and got.shape == x.shape
        ref, _ = apu.offload(stages, (x,), mode="eager")
        # a padded batch and a lone request may sum in another order on
        # the CPU; bit-identity with the device rows is pinned above
        np.testing.assert_allclose(got, np.asarray(ref[0].data), rtol=1e-6)


def test_batched_stages_scale_counts():
    stages = _mm_stages()
    bs = batched_stages(stages, batch=4)
    base = stages[0].kernel.counts()
    scaled = bs[0].kernel.counts()
    assert scaled.ops == 4 * base.ops
    assert scaled.host_bytes == 4 * base.host_bytes


# ---------------------------------------------------------------------------
# Warm server: zero re-captures, bit-identical results (acceptance)
# ---------------------------------------------------------------------------
def test_warm_server_zero_recaptures_and_bit_identical():
    stages = _mm_stages(n=2)
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=2, max_in_flight=2)
    rng = np.random.default_rng(3)
    rids = []
    for _ in range(8):                   # 4 full batches, one bucket
        x = jnp.asarray(rng.standard_normal(
            (int(rng.integers(3, 9)), 8)), jnp.float32)
        rids.append((srv.submit(x), x))
    srv.flush()
    # ZERO re-captures after the first: one bucket x one worker = 1 miss
    assert srv.cache.misses == 1
    assert srv.cache.hits == 3
    # batched results bit-identical to per-request eager offload
    apu = APU(EGPU_16T)
    for rid, x in rids:
        (got,) = srv.result(rid)
        ref, _ = apu.offload(stages, (x,), mode="eager")
        assert np.array_equal(np.asarray(got), np.asarray(ref[0].data))


def test_server_warmup_precaptures_every_bucket_worker_pair():
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T, EGPU_8T), bucket_sizes=(4, 8),
                 max_batch=2)
    captured = srv.warmup(jnp.zeros((1, 8), jnp.float32))
    assert captured == 4                 # 2 buckets x 2 workers
    assert srv.warmup(jnp.zeros((1, 8), jnp.float32)) == 0   # idempotent
    rng = np.random.default_rng(5)
    for _ in range(12):
        srv.submit(jnp.asarray(rng.standard_normal(
            (int(rng.integers(1, 9)), 8)), jnp.float32))
    srv.flush()
    assert srv.cache.misses == 4         # nothing re-captured after warmup
    rep = srv.report()
    assert rep.n_requests == 12
    assert rep.modeled_latency_s[50] > 0.0
    assert rep.cache["misses"] == 4
    assert sum(q.requests for q in rep.queues) == 12
    assert len(rep.summary()) > 0


def test_server_report_percentiles_ordered():
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(4, 32),
                 max_batch=2)
    rng = np.random.default_rng(9)
    for n in (2, 2, 30, 30, 3, 3):       # two buckets => two latency classes
        srv.submit(jnp.asarray(rng.standard_normal((n, 8)), jnp.float32))
    srv.flush()
    rep = srv.report()
    assert (rep.modeled_latency_s[50] <= rep.modeled_latency_s[90]
            <= rep.modeled_latency_s[99])
    assert rep.modeled_cost_per_request_s <= rep.modeled_latency_s[99]


# ---------------------------------------------------------------------------
# Multi-queue dispatch + backpressure
# ---------------------------------------------------------------------------
def test_dispatcher_balances_and_bounds_in_flight():
    """Homogeneous lanes split traffic evenly (modeled speeds tie, so the
    requests-served fallback alternates); the in-flight bound holds."""
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T, EGPU_16T), bucket_sizes=(8,),
                 max_batch=1, max_in_flight=2)
    rng = np.random.default_rng(11)
    for _ in range(10):
        srv.submit(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    srv.flush()
    rep = srv.report()
    per_worker = {q.name: q for q in rep.queues}
    assert len(per_worker) == 2
    # equal-speed routing splits a 10-batch stream across both lanes
    assert all(q.batches == 5 for q in rep.queues)
    # the in-flight window is respected and backpressure engaged
    assert all(q.peak_in_flight <= 2 for q in rep.queues)
    assert all(q.backpressure_stalls > 0 for q in rep.queues)
    assert all(w.depth == 0 for w in srv.dispatcher.workers)   # drained


def test_dispatcher_heterogeneous_mix_favors_modeled_faster_lane():
    """A 16T lane models faster per request than an 8T one, so it wins
    depth ties and attracts more traffic — while the slow lane still
    bootstraps and serves."""
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_8T, EGPU_16T), bucket_sizes=(8,),
                 max_batch=1, max_in_flight=2)
    rng = np.random.default_rng(11)
    for _ in range(10):
        srv.submit(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    srv.flush()
    per = {q.config: q for q in srv.report().queues}
    assert per["e-gpu-16t"].batches > per["e-gpu-8t"].batches
    assert per["e-gpu-8t"].batches >= 1
    assert all(q.peak_in_flight <= 2 for q in srv.report().queues)


def test_pick_tiebreak_uses_modeled_speed_not_requests_served():
    """Regression (ISSUE 5): after a warmup imbalance, the 16T worker has
    served MORE requests than the 8T one — under the old raw-n_requests
    tie-break it lost every depth tie from then on, permanently routing
    new traffic to the slower lane.  The tie must go to the lane with the
    lower modeled seconds-per-request."""
    stages = _mm_stages()
    slow = QueueWorker(EGPU_8T, name="slow")
    fast = QueueWorker(EGPU_16T, name="fast")
    srv = Server(stages, workers=(slow, fast), bucket_sizes=(8,),
                 max_batch=1)
    dispatcher = srv.dispatcher

    def submit_to(worker):
        x = jnp.ones((8, 8), jnp.float32)
        batch = srv.batcher._collate(
            srv.batcher.bucket_key_for((x,)),
            [srv.batcher.submit(x)])
        graph, _ = srv.cache.get_or_capture(
            worker.apu, srv._bstages, batch.inputs, key_prefix=srv._bsig)
        worker.launch(graph, batch)

    # warmup: one batch each, plus ONE extra on the fast worker
    submit_to(slow)
    submit_to(fast)
    submit_to(fast)
    for w in dispatcher.workers:
        w.drain()
    assert fast.n_requests > slow.n_requests       # the historical trap
    assert all(w.depth == 0 for w in dispatcher.workers)
    spr = {w.name: w.modeled_s_per_request() for w in dispatcher.workers}
    assert spr["fast"] < spr["slow"]
    # equal depth, model data on both: the FAST lane must win the tie
    assert dispatcher.pick() is fast


def test_pick_falls_back_to_requests_served_without_model_data():
    """Cold workers (no modeled launch yet) keep the original
    least-requests-served tie-break, and are preferred over warm lanes at
    equal depth so every lane bootstraps its model."""
    cold_a = QueueWorker(EGPU_16T, name="a")
    cold_b = QueueWorker(EGPU_8T, name="b")
    d = MultiQueueDispatcher([cold_a, cold_b])
    assert d.pick() is cold_a                      # stable order on full tie
    cold_a.n_requests = 3                          # simulate served history
    assert d.pick() is cold_b                      # fewer requests wins
    cold_a.n_requests = 0
    cold_b.n_requests = 5
    cold_b.modeled_s = 1e-3                        # b warms up
    assert d.pick() is cold_a                      # cold lane bootstraps first


def test_retire_releases_only_own_event_segment():
    """Retiring the oldest of two in-flight launches on ONE cached graph
    must not drain or release the newer launch's events — and every event
    lives on the WORKER's queue (launch-time binding), never on the cached
    graph's capture queue."""
    stages = _mm_stages(n=2)
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=1, max_in_flight=2)
    rng = np.random.default_rng(13)
    for _ in range(2):                   # two launches, same bucket/graph
        srv.submit(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    (worker,) = srv.dispatcher.workers
    assert worker.depth == 2
    (graph,) = srv.cache._graphs.values()
    # host API v2: the worker's capture brackets the 2 kernel stages with
    # explicit write (inputs) and read (outputs) transfer nodes
    n_nodes = len(graph.nodes)
    assert n_nodes == 4
    assert [n.kind for n in graph.nodes] == ["write", "kernel", "kernel",
                                             "read"]
    retired = worker._retire_oldest()
    assert retired.n_events == n_nodes
    # exactly one launch's segment released; the in-flight one retained
    assert worker.queue.released_count == n_nodes
    assert len(worker.queue.events) == n_nodes
    # the cached graph's own capture queue saw none of it
    assert graph.queue.events == () and graph.queue.released_count == 0
    srv.flush()
    assert (worker.queue.released_count == 2 * n_nodes
            and worker.queue.events == ())


def test_worker_rejects_bad_config():
    with pytest.raises(ValueError):
        QueueWorker(EGPU_16T, max_in_flight=0)
    with pytest.raises(ValueError):
        MultiQueueDispatcher([])
    w1, w2 = QueueWorker(EGPU_16T, name="a"), QueueWorker(EGPU_8T, name="a")
    with pytest.raises(ValueError):
        MultiQueueDispatcher([w1, w2])


# ---------------------------------------------------------------------------
# Event lifecycle: bounded profiling window, retain, accounting
# ---------------------------------------------------------------------------
def _counts_kernel():
    return Kernel(
        "twice", executor=lambda x: x * 2,
        counts=lambda **kw: WorkCounts(ops=64, dcache_bytes=256,
                                       host_bytes=256, working_set=256))


def test_release_events_preserves_totals():
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx)
    a = ctx.create_buffer(jnp.ones(64, jnp.float32))
    for _ in range(4):
        q.enqueue_nd_range(_counts_kernel(), NDR, (a,))
    q.finish()
    before_s, before_j = q.total_modeled_s(), q.total_energy_j()
    assert before_s > 0
    n = q.release_events()
    assert n == 4 and q.events == () and q.released_count == 4
    assert q.total_modeled_s() == pytest.approx(before_s)
    assert q.total_energy_j() == pytest.approx(before_j)


def test_release_events_skips_undrained():
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx)
    a = ctx.create_buffer(jnp.ones(64, jnp.float32))
    q.enqueue_nd_range(_counts_kernel(), NDR, (a,))
    q.finish()
    ev = q.enqueue_nd_range(_counts_kernel(), NDR, (a,))   # in flight
    assert q.release_events() == 1       # only the drained one
    assert q.events == (ev,) and not ev.released
    q.finish()


def test_bounded_profiling_window():
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx, max_events=2)
    a = ctx.create_buffer(jnp.ones(64, jnp.float32))
    evs = []
    for i in range(5):
        evs.append(q.enqueue_nd_range(_counts_kernel(), NDR, (a,)))
        q.finish()
    assert len(q.events) == 2            # window, not full history
    assert q.released_count == 3
    # totals still cover all five launches
    one = evs[0].modeled.total_s
    assert q.total_modeled_s() == pytest.approx(5 * one)


def test_event_retain_survives_queue_release():
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx)
    a = ctx.create_buffer(jnp.ones(64, jnp.float32))
    kept = q.enqueue_nd_range(_counts_kernel(), NDR, (a,)).retain()
    dropped = q.enqueue_nd_range(_counts_kernel(), NDR, (a,))
    q.finish()
    q.release_events()
    assert dropped.released and dropped.outputs == ()
    assert not kept.released and len(kept.outputs) == 1    # holder's ref
    kept.release()
    assert kept.released and kept.outputs == ()
    with pytest.raises(RuntimeError):
        kept.retain()


def test_launch_prefix_replaces_leading_externals_only():
    apu = APU(EGPU_16T)
    stages = _mm_stages()
    x = _x()
    graph = apu.capture_pipeline(stages, (x,))
    assert graph.n_request_inputs == 1
    y = _x(seed=9)
    (out,) = graph.launch_prefix((y,), queue_events=False)
    w = stages[0].consts[0]
    np.testing.assert_array_equal(
        np.asarray(out.data),
        np.asarray(jnp.maximum(gemm_ref(y, w), 0.0)))
    with pytest.raises(ValueError):
        graph.launch_prefix((y, y, y))   # more inputs than externals
    with pytest.raises(ValueError):
        # donating a non-replaced position would consume the captured
        # constant buffer every later launch still needs
        graph.launch_prefix((y,), donate=(1,))
    # fused accounting is memoized and launch-invariant
    assert graph.fused_modeled() is graph.fused_modeled()


def test_server_results_store_bounded_by_metrics_window():
    """Regression (ISSUE 5): completed-but-never-fetched results must not
    accumulate forever — the store is bounded to `metrics_window` and an
    evicted read raises the flush-the-server KeyError with an explicit
    eviction hint."""
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=1, metrics_window=4)
    rng = np.random.default_rng(21)
    rids = []
    for _ in range(10):                  # > window, nothing ever fetched
        rids.append(srv.submit(
            jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)))
    srv.flush()
    assert len(srv._results) == 4        # O(window), not O(traffic)
    rep = srv.report()
    assert rep.results_evicted == 6
    assert "6 unread results evicted" in rep.summary()
    # the newest `window` results are still readable
    for rid in rids[-4:]:
        (out,) = srv.result(rid)
        assert out.shape == (8, 8)
    # an evicted rid raises the existing KeyError, now with the hint
    with pytest.raises(KeyError, match="evicted"):
        srv.result(rids[0])
    # an id that was READ (not evicted) keeps the plain message
    with pytest.raises(KeyError) as exc:
        srv.result(rids[-1])
    assert "flush" in str(exc.value)


def test_server_results_keep_refreshes_lru():
    """keep=True is a real LRU touch: an actively-polled result must not
    age out behind completions that arrived after its last read."""
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=1, metrics_window=3)
    rng = np.random.default_rng(23)

    def one():
        return srv.submit(
            jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))

    kept = one()
    srv.flush()
    for _ in range(4):                   # > window newer completions, but
        one()                            # the kept rid is re-read each round
        srv.flush()
        (out,) = srv.result(kept, keep=True)
    assert out.shape == (8, 8)           # still readable: LRU refreshed
    (final,) = srv.result(kept)          # and still poppable at the end
    np.testing.assert_array_equal(np.asarray(out), np.asarray(final))


def test_oversize_request_unified_error_at_submit():
    """Regression (ISSUE 5): both historical oversize paths — the bare
    bucket_size_for ValueError and pad_to's extent/target mismatch — are
    replaced by ONE submit-time error naming the array index, axis,
    extent and largest configured bucket."""
    b = BucketBatcher((4, 8), max_batch=2)
    # path 1: single-array request, pad-axis extent exceeds every bucket
    with pytest.raises(ValueError, match=(
            r"array 0 has extent 9 along pad_axis 0.*largest configured "
            r"bucket 8")):
        b.submit(jnp.zeros(9, jnp.float32))
    # path 2: multi-array request — the offending array is NAMED, instead
    # of a later pad_to failure with no request context
    with pytest.raises(ValueError, match=(
            r"array 1 has extent 12 along pad_axis 0.*largest configured "
            r"bucket 8")):
        b.submit(jnp.zeros(3, jnp.float32), jnp.zeros(12, jnp.float32))
    assert b.n_pending == 0              # nothing half-staged
    # the same unified error surfaces through Server.submit
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,), max_batch=1)
    with pytest.raises(ValueError, match="oversize request: array 0"):
        srv.submit(jnp.zeros((99, 8), jnp.float32))
    # pad_to itself stays loud (and now names the axis) for direct callers
    from repro.serve import pad_to
    with pytest.raises(ValueError, match="extent 9 along axis 0"):
        pad_to(jnp.zeros(9, jnp.float32), 8)


def test_server_result_pops_by_default():
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,), max_batch=1)
    rid = srv.submit(jnp.ones((8, 8), jnp.float32))
    srv.flush()
    (out,) = srv.result(rid, keep=True)
    (again,) = srv.result(rid)           # keep=True left it readable
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))
    with pytest.raises(KeyError):
        srv.result(rid)                  # default read popped it


# ---------------------------------------------------------------------------
# Open-loop front door (ISSUE 6): satellites + SLO intake
# ---------------------------------------------------------------------------
class _Boom:
    """A buffer payload whose realization fails (simulated device fault)."""

    def block_until_ready(self):
        raise RuntimeError("simulated realization failure")


def test_retire_drains_segment_even_when_realization_raises():
    """Regression (ISSUE 6): if block_until_ready() raises inside
    _retire_oldest, the ticket is already popped — the drain/release of
    its event segment must STILL run (finally), or the lane's per-queue
    accounting is permanently skewed against every later ticket."""
    stages = _mm_stages(n=2)
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=1, max_in_flight=2)
    rng = np.random.default_rng(13)
    for _ in range(2):
        srv.submit(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    (worker,) = srv.dispatcher.workers
    assert worker.depth == 2
    oldest = worker._inflight[0]
    n = oldest.n_events
    oldest.outputs[0].data = _Boom()     # poison the oldest ticket
    with pytest.raises(RuntimeError, match="simulated realization failure"):
        worker._retire_oldest()
    # the failure propagated, but the segment was drained + released
    assert worker.queue.released_count == n
    assert worker.depth == 1
    # the lane is NOT poisoned: later tickets retire with exact accounting
    srv.submit(jnp.asarray(rng.standard_normal((8, 8)), jnp.float32))
    srv.flush()
    assert worker.queue.released_count == 3 * n
    assert worker.queue.events == () and worker.depth == 0


def test_batcher_rejects_malformed_construction():
    """Satellite (ISSUE 6): unsorted / duplicate / non-positive bucket
    lists and max_batch < 1 fail loudly at construction, not obscurely at
    bucket-selection time."""
    with pytest.raises(ValueError, match="ascending"):
        BucketBatcher((256, 64, 1024))
    with pytest.raises(ValueError, match="duplicate"):
        BucketBatcher((64, 64, 256))
    with pytest.raises(ValueError, match="positive"):
        BucketBatcher((0, 64))
    with pytest.raises(ValueError, match="positive"):
        BucketBatcher((-4, 64))
    with pytest.raises(ValueError, match="at least one bucket"):
        BucketBatcher(())
    with pytest.raises(ValueError, match="max_batch"):
        BucketBatcher((64,), max_batch=0)
    # well-formed input still constructs
    assert BucketBatcher((64, 256)).bucket_sizes == (64, 256)


def test_rejected_first_submit_does_not_start_wall_clock():
    """Satellite (ISSUE 6): _t0 is stamped only once a request is actually
    ACCEPTED — a server whose first submit is rejected (oversize) must not
    charge the idle gap before the first real request to its wall clock."""
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,), max_batch=1)
    with pytest.raises(ValueError, match="oversize"):
        srv.submit(jnp.zeros((99, 8), jnp.float32))
    assert srv._t0 is None               # clock never started
    srv.submit(jnp.ones((8, 8), jnp.float32))
    assert srv._t0 is not None
    srv.flush()
    assert srv.report().n_requests == 1


def test_admission_sheds_when_queue_full_and_preempts_by_priority():
    """max_pending bounds the staged queue: an equal-priority submit sheds
    loudly; a HIGHER-priority submit preempts the lowest-priority pending
    request instead (whose result() then raises AdmissionError)."""
    from repro.serve import AdmissionError
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=8, max_pending=2)
    r0 = srv.submit(jnp.ones((8, 8), jnp.float32), priority=0)
    r1 = srv.submit(jnp.ones((8, 8), jnp.float32), priority=1)
    # queue full, same priority as the weakest pending: shed at the door
    with pytest.raises(AdmissionError, match="max_pending"):
        srv.submit(jnp.ones((8, 8), jnp.float32), priority=0)
    assert srv.n_shed == 1
    # higher priority: preempts r0 (lowest priority pending) and is admitted
    r2 = srv.submit(2.0 * jnp.ones((8, 8), jnp.float32), priority=5)
    assert srv.batcher.n_pending == 2
    srv.flush()
    with pytest.raises(AdmissionError, match="preempted"):
        srv.result(r0)
    for rid in (r1, r2):
        (out,) = srv.result(rid)
        assert np.asarray(out).shape == (8, 8)
    rep = srv.report()
    assert rep.n_shed == 2 and rep.n_requests == 2


def test_admission_sheds_infeasible_deadline_and_deadline_flush():
    """Modeled-capacity admission: once the fleet is profiled, a deadline
    budget smaller than the predicted completion sheds at the door; a
    feasible deadline-carrying request launches its PARTIAL bucket when
    the budget is at risk (tick), instead of waiting for capacity."""
    from repro.serve import AdmissionError
    stages = _mm_stages()
    t = [0.0]
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=4, clock=lambda: t[0])
    # profile the lane: one full batch through
    for _ in range(4):
        srv.submit(jnp.ones((8, 8), jnp.float32))
    srv.flush()
    assert srv.report().n_requests == 4
    spr = srv.dispatcher.workers[0].modeled_s_per_request()
    assert spr is not None and spr > 0
    # an absurdly tight budget is infeasible -> shed loudly
    with pytest.raises(AdmissionError, match="deadline budget"):
        srv.submit(jnp.ones((8, 8), jnp.float32), deadline=spr * 1e-6)
    assert srv.n_shed == 1
    # a feasible budget is admitted; advancing the clock to the at-risk
    # point deadline-flushes the partial (1/4-full) bucket
    rid = srv.submit(jnp.ones((8, 8), jnp.float32), deadline=1000.0 * spr)
    assert srv.batcher.n_pending == 1
    t[0] += 999.0 * spr
    srv.tick()
    assert srv.batcher.n_pending == 0
    assert srv.batcher.deadline_flushes == 1
    srv.flush()
    (out,) = srv.result(rid)
    assert np.asarray(out).shape == (8, 8)
    assert srv.report().deadline_flushes == 1


def test_deadline_validation_and_violation_accounting():
    """deadline must be a positive budget; a request whose modeled
    completion exceeds its absolute deadline counts as a violation in the
    report (completed late, not shed)."""
    stages = _mm_stages()
    t = [0.0]
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=1, admission=False, clock=lambda: t[0])
    with pytest.raises(ValueError, match="positive budget"):
        srv.submit(jnp.ones((8, 8), jnp.float32), deadline=-1.0)
    # admission off: an infeasible deadline is ACCEPTED, completes late
    rid = srv.submit(jnp.ones((8, 8), jnp.float32), deadline=1e-12)
    srv.flush()
    (out,) = srv.result(rid)             # still completes, bit-identical
    assert np.asarray(out).shape == (8, 8)
    rep = srv.report()
    assert rep.n_deadline_violations == 1
    assert rep.n_shed == 0
