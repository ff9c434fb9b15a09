"""Event-dependency DAGs, out-of-order queues, and launch-time queue
binding (ISSUE 3).

Pins the new execution-model contracts: explicit ``wait_events`` edges and
marker/barrier analogues, out-of-order capture producing dependency DAGs
whose fused modeled latency is the critical path (concurrent branches
overlap), multi-queue captures (host + e-GPU nodes in one graph), and the
shared-cache accounting fix — launches of a cached ``CommandGraph`` bind
their events and modeled totals to the *launching* queue, so same-config
workers sharing one cache entry keep exact per-queue histories.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EGPU_16T, HOST, CommandQueue, Context, Device,
                        Event, Kernel, NDRange, PhaseBreakdown, Stage,
                        fuse_breakdowns)
from repro.kernels.gemm.ref import counts as gemm_counts
from repro.kernels.gemm.ref import gemm_ref
from repro.serve import BucketBatcher, GraphCache, QueueWorker

NDR = NDRange((8, 8), (4, 4))


def _ctx():
    return Context(Device(EGPU_16T))


def _mm_kernel(name="mm"):
    return Kernel(name=name, executor=gemm_ref,
                  counts=lambda **kw: gemm_counts(m=8, n=8, k=8))


def _x(seed=0, shape=(8, 8)):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


# ---------------------------------------------------------------------------
# Out-of-order queues: dependency edges under capture
# ---------------------------------------------------------------------------
def test_out_of_order_independent_launches_are_unordered():
    """No wait_events + no dataflow link = no edge: the two launches are
    concurrent, and the fused critical path is a max, not a sum."""
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, b))
    assert g.node_deps() == ((), ())
    fused, _ = g.fused_modeled()
    chain = fuse_breakdowns(g.modeled_breakdowns())
    assert fused.total_s < chain.total_s
    # both launches still execute (recorded order) and produce real results
    o = g.launch()
    assert len(o) == 1                   # graph outputs = last node's


def test_in_order_capture_keeps_implicit_chain():
    """The default queue chains launches even without dataflow between
    them — classic in-order OpenCL semantics."""
    ctx = _ctx()
    q = CommandQueue(ctx)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, b))
    assert g.node_deps() == ((), (0,))
    fused, _ = g.fused_modeled()
    chain = fuse_breakdowns(g.modeled_breakdowns())
    assert fused.total_s == chain.total_s


def test_wait_events_add_edges_beyond_dataflow():
    """An explicit wait list orders nodes that share no buffers."""
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        e0 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, b), wait_events=[e0])
    assert g.node_deps() == ((), (0,))


def test_diamond_dag_critical_path_and_eager_identity():
    """Acceptance: a diamond (A -> B, A -> C, {B,C} -> D) captured on an
    out-of-order queue models critical-path latency strictly below the
    chain-sum while launching bit-identical to eager execution."""
    ctx = _ctx()
    x = _x(3)
    q = CommandQueue(ctx, out_of_order=True)
    with q.capture() as g:
        a = ctx.create_buffer(x)
        e0 = q.enqueue_nd_range(_mm_kernel("A"), NDR, (a, a))
        e1 = q.enqueue_nd_range(_mm_kernel("B"), NDR, e0.outputs + (a,),
                                wait_events=[e0])
        e2 = q.enqueue_nd_range(_mm_kernel("C"), NDR, e0.outputs + (a,),
                                wait_events=[e0])
        q.enqueue_nd_range(_mm_kernel("D"), NDR,
                           (e1.outputs[0], e2.outputs[0]),
                           wait_events=[e1, e2])
    assert g.node_deps() == ((), (0,), (0,), (1, 2))
    fused, _ = g.fused_modeled()
    chain = fuse_breakdowns(g.modeled_breakdowns())
    assert fused.total_s < chain.total_s         # one branch overlaps
    # work phases on the path: A + one branch + D (3 of 4 equal-cost nodes)
    per = g.nodes[0].modeled
    assert fused.compute == pytest.approx(3 * per.compute)
    assert chain.compute == pytest.approx(4 * per.compute)
    # bit-identical to eager dispatch of the same dataflow
    qe = CommandQueue(ctx, out_of_order=True, profile=False)
    ae = ctx.create_buffer(x)
    f0 = qe.enqueue_nd_range(_mm_kernel("A"), NDR, (ae, ae))
    f1 = qe.enqueue_nd_range(_mm_kernel("B"), NDR, f0.outputs + (ae,),
                             wait_events=[f0])
    f2 = qe.enqueue_nd_range(_mm_kernel("C"), NDR, f0.outputs + (ae,),
                             wait_events=[f0])
    f3 = qe.enqueue_nd_range(_mm_kernel("D"), NDR,
                             (f1.outputs[0], f2.outputs[0]),
                             wait_events=[f1, f2])
    (eager,) = f3.wait()
    (fused_out,) = g.launch()
    assert np.array_equal(np.asarray(fused_out.data), np.asarray(eager.data))


# ---------------------------------------------------------------------------
# Markers and barriers
# ---------------------------------------------------------------------------
def test_marker_aggregates_dependencies_under_capture():
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        e0 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        e1 = q.enqueue_nd_range(_mm_kernel(), NDR, (b, b))
        m = q.enqueue_marker(wait_events=[e0, e1])
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, b), wait_events=[m])
    # the marker is a zero-cost node fanning both edges in; the final
    # kernel reaches them transitively through it
    assert g.node_deps() == ((), (), (0, 1), (2,))


def test_barrier_orders_out_of_order_capture():
    """Launches after a barrier implicitly depend on everything before it,
    even on an out-of-order queue; launches after the barrier stay
    unordered among themselves."""
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, b))
        q.enqueue_barrier()
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, b))
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, a))
    # the barrier node aggregates everything before it; both later
    # launches order through it (and not through each other)
    assert g.node_deps() == ((), (), (0, 1), (2,), (2,))


def test_empty_wait_list_means_all_previous():
    """OpenCL: a marker/barrier with an EMPTY wait list waits on all
    previously enqueued commands, exactly like passing none at all — an
    empty-list barrier must not erase the ordering frontier."""
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    a, b = ctx.create_buffer(_x(1)), ctx.create_buffer(_x(2))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_barrier(wait_events=[])
        q.enqueue_nd_range(_mm_kernel(), NDR, (b, b))
    assert g.node_deps() == ((), (0,), (1,))
    # eager: empty-list marker still aggregates the queue's history
    qe = CommandQueue(ctx, profile=False, out_of_order=True)
    e0 = qe.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    m = qe.enqueue_marker(wait_events=[])
    assert e0 in m.deps
    qe.finish()


def test_in_order_capture_barrier_carries_cross_queue_edges():
    """A barrier's wait list can point at nodes of a JOINED queue the
    in-order chain doesn't cover — the edge must survive into the DAG."""
    ctx = _ctx()
    host_q = CommandQueue(Context(Device(HOST)))
    q = CommandQueue(ctx)                # in-order
    a = ctx.create_buffer(_x(1))
    with q.capture() as g:
        with g.join(host_q):
            host_ev = host_q.enqueue_nd_range(_mm_kernel("host"), NDR, (a, a))
        q.enqueue_barrier(wait_events=[host_ev])
        q.enqueue_nd_range(_mm_kernel("egpu"), NDR, (a, a))   # no dataflow
    # the barrier node carries the cross-queue edge; the e-GPU kernel
    # orders after the host node THROUGH it (was silently dropped)
    assert g.node_deps() == ((), (0,), (1,))
    # the in-order chain carries it transitively to later nodes too
    with q.capture() as g2:
        with g2.join(host_q):
            hev = host_q.enqueue_nd_range(_mm_kernel("host"), NDR, (a, a))
        q.enqueue_barrier(wait_events=[hev])
        q.enqueue_nd_range(_mm_kernel("e1"), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel("e2"), NDR, (a, a))
    assert g2.node_deps() == ((), (0,), (1,), (2,))


def test_consecutive_sync_commands_accumulate_frontier():
    """A marker (or second barrier) between a barrier and the next launch
    must not erase the barrier's cross-queue edges — sync commands merge
    their constraints, they never cancel earlier ones."""
    ctx = _ctx()
    host_q = CommandQueue(Context(Device(HOST)))
    q = CommandQueue(ctx)                # in-order
    a = ctx.create_buffer(_x(1))
    with q.capture() as g:
        with g.join(host_q):
            host_ev = host_q.enqueue_nd_range(_mm_kernel("host"), NDR, (a, a))
        q.enqueue_barrier(wait_events=[host_ev])
        q.enqueue_marker()               # all-so-far: includes the barrier
        q.enqueue_nd_range(_mm_kernel("egpu"), NDR, (a, a))
    # host -> barrier -> marker -> kernel: the cross-queue edge survives
    # the interposed marker via transitivity
    assert g.node_deps() == ((), (0,), (1,), (2,))
    # out-of-order: both barriers' constraints reach later launches (the
    # second barrier chains to the first via the queue's barrier point)
    q2 = CommandQueue(ctx, out_of_order=True)
    b = ctx.create_buffer(_x(2))
    with q2.capture() as g2:
        with g2.join(host_q):
            hev = host_q.enqueue_nd_range(_mm_kernel("host"), NDR, (a, a))
        q2.enqueue_barrier(wait_events=[hev])
        e1 = q2.enqueue_nd_range(_mm_kernel("e1"), NDR, (a, a))
        q2.enqueue_barrier(wait_events=[e1])
        q2.enqueue_nd_range(_mm_kernel("e2"), NDR, (b, b))
    assert g2.node_deps() == ((), (0,), (1,), (1, 2), (3,))


def test_trailing_barrier_does_not_eat_graph_outputs():
    ctx = _ctx()
    q = CommandQueue(ctx)
    a = ctx.create_buffer(_x(1))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_barrier()              # zero-cost node, no outputs
    (out,) = g.launch()                  # outputs: last KERNEL node's
    assert out.shape == (8, 8)
    # a capture holding only sync commands has nothing to launch
    with q.capture() as g2:
        q.enqueue_marker()
    with pytest.raises(RuntimeError):
        g2.launch()


def test_marker_and_barrier_eager_semantics():
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True, profile=False)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    e0 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    m = q.enqueue_marker()               # waits everything enqueued so far
    assert e0 in m.deps
    bar = q.enqueue_barrier()
    e1 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    assert bar in e1.deps                # out-of-order: barrier edge only
    m.wait()
    assert e0.done                       # marker realized its dependencies
    q.finish()
    assert e1.done


# ---------------------------------------------------------------------------
# Multi-queue capture: host + e-GPU nodes in one graph
# ---------------------------------------------------------------------------
def test_join_captures_host_and_egpu_nodes_in_one_graph():
    ctx = _ctx()
    host_q = CommandQueue(Context(Device(HOST)))
    q = CommandQueue(ctx)
    x = _x(4)
    with q.capture() as g:
        a = ctx.create_buffer(x)
        e0 = q.enqueue_nd_range(_mm_kernel("egpu_mm"), NDR, (a, a))
        with g.join(host_q):
            host_q.enqueue_nd_range(_mm_kernel("host_mm"), NDR,
                                    e0.outputs + (a,), wait_events=[e0])
    assert len(g.nodes) == 2 and g.node_deps() == ((), (0,))
    assert q in g.queues and host_q in g.queues
    # each node costed on ITS queue's device: the e-GPU node pays
    # Tiny-OpenCL startup + scheduling, the scalar host does not
    assert g.nodes[0].modeled.scheduling > 0.0
    assert g.nodes[1].modeled.scheduling == 0.0
    fused, _ = g.fused_modeled()         # DAG mode fuses across devices
    assert fused.total_s > 0.0
    (out,) = g.launch()
    np.testing.assert_allclose(np.asarray(out.data),
                               np.asarray(gemm_ref(gemm_ref(x, x), x)),
                               atol=1e-4)
    # joining outside an active capture is rejected
    with pytest.raises(RuntimeError):
        with g.join(host_q):
            pass


def test_join_of_already_capturing_queue_keeps_capture_alive():
    """A redundant join (the capture's own queue, or a nested join) must
    not end that queue's capture when the inner block closes."""
    ctx = _ctx()
    q = CommandQueue(ctx)
    a = ctx.create_buffer(_x(6))
    with q.capture() as g:
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        with g.join(q):                  # q is already capturing g
            q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        # capture must still be live: this enqueue is RECORDED, not run
        ev = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        assert getattr(ev, "_graph", None) is g
    assert len(g.nodes) == 3 and q.events == ()
    assert len(g.launch()) == 1


# ---------------------------------------------------------------------------
# Launch-time queue binding
# ---------------------------------------------------------------------------
def test_launch_binds_events_to_caller_queue():
    ctx = _ctx()
    q = CommandQueue(ctx)
    a = ctx.create_buffer(_x(5))
    with q.capture() as g:
        e = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
        q.enqueue_nd_range(_mm_kernel(), NDR, e.outputs + (a,))
    mine = CommandQueue(ctx)
    outs = g.launch(queue=mine)
    mine.finish()
    assert len(mine.events) == 2
    assert q.events == ()                # capture queue untouched
    # launch outputs carry the producing event, so a later eager consumer
    # gets the same dataflow ordering edge as enqueue outputs
    assert all(getattr(b, "_event", None) is mine.events[-1] for b in outs)
    assert mine.total_modeled_s() == pytest.approx(g.total_modeled_s())
    # default launch still lands on the capture (home) queue
    g.launch()
    q.finish()
    assert len(q.events) == 2


def test_shared_cache_two_workers_exact_per_queue_accounting():
    """Acceptance: two same-config workers share ONE cached graph;
    interleaved launches book events and modeled totals on each worker's
    own queue exactly — nothing ever lands on a sibling or on the cached
    graph's capture queue."""
    rng = np.random.default_rng(17)
    d = 8
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)
    kern = Kernel("mlp", executor=lambda x, w: jnp.maximum(gemm_ref(x, w), 0.0),
                  counts=lambda **kw: gemm_counts(m=d, n=d, k=d))
    stages = [Stage(kern, consts=(w,), n_inputs=1) for _ in range(2)]

    cache = GraphCache(capacity=4)
    w1 = QueueWorker(EGPU_16T, name="w1", max_in_flight=8)
    w2 = QueueWorker(EGPU_16T, name="w2", max_in_flight=8)

    def make_batch(seed):
        b = BucketBatcher((d,), max_batch=1)
        b.submit(jnp.asarray(rng.standard_normal((d, d)), jnp.float32))
        (mb,) = b.drain()
        return mb

    batches = [make_batch(i) for i in range(5)]
    g1, hit1 = cache.get_or_capture(w1.apu, stages, batches[0].inputs)
    g2, hit2 = cache.get_or_capture(w2.apu, stages, batches[0].inputs)
    assert g1 is g2 and not hit1 and hit2      # genuinely shared entry

    plan = [w1, w2, w1, w2, w1]                # interleaved: 3 vs 2
    for worker, mb in zip(plan, batches):
        worker.launch(g1, mb)
    w1.drain()
    w2.drain()

    n_nodes = len(g1.nodes)
    per_launch_s = g1.total_modeled_s()
    per_launch_j = g1.total_energy_j()
    # each queue's history/totals contain exactly its OWN launches
    assert w1.queue.released_count == 3 * n_nodes
    assert w2.queue.released_count == 2 * n_nodes
    assert w1.queue.total_modeled_s() == pytest.approx(3 * per_launch_s)
    assert w2.queue.total_modeled_s() == pytest.approx(2 * per_launch_s)
    assert w1.queue.total_energy_j() == pytest.approx(3 * per_launch_j)
    assert w2.queue.total_energy_j() == pytest.approx(2 * per_launch_j)
    # the shared graph's capture queue never saw a launch
    assert g1.queue.events == () and g1.queue.released_count == 0
    assert g1.queue.total_modeled_s() == 0.0
    # worker roll-ups agree with their queues' launch counts
    assert (w1.n_batches, w2.n_batches) == (3, 2)


# ---------------------------------------------------------------------------
# fuse_breakdowns: DAG mode semantics and validation
# ---------------------------------------------------------------------------
def _pb(compute, freq=300e6, startup=10.0, sched=20.0, transfer=5.0):
    return PhaseBreakdown(startup=startup, scheduling=sched,
                          transfer=transfer, compute=compute, freq_hz=freq)


def test_fuse_dag_linear_chain_matches_chain_mode():
    stages = [_pb(100.0), _pb(200.0), _pb(300.0)]
    chain = fuse_breakdowns(stages)
    dag = fuse_breakdowns(stages, deps=[(), (0,), (1,)])
    assert dag == chain                  # exact: same dataclass fields


def test_fuse_dag_parallel_branches_take_max():
    stages = [_pb(100.0), _pb(400.0)]
    dag = fuse_breakdowns(stages, deps=[(), ()])
    # unordered: the critical path is the heavier branch alone
    assert dag.compute == 400.0 and dag.transfer == 5.0
    assert dag.startup == 10.0 and dag.scheduling == 20.0


def test_fuse_dag_mixed_frequencies_normalize():
    # 1 us @ 300 MHz feeding 1 us @ 150 MHz = 2 us end to end
    a = _pb(300.0, freq=300e6, startup=0.0, sched=0.0, transfer=0.0)
    b = _pb(150.0, freq=150e6, startup=0.0, sched=0.0, transfer=0.0)
    dag = fuse_breakdowns([a, b], deps=[(), (0,)])
    assert dag.total_s == pytest.approx(2e-6)
    assert dag.freq_hz == 300e6          # normalized to the fastest clock
    # chain mode normalizes per stage too (ISSUE 8: stages priced at
    # different DVFS operating points fuse instead of raising) and agrees
    # with the linear DAG exactly
    chain = fuse_breakdowns([a, b])
    assert chain == dag


def test_fuse_dag_none_stages_are_zero_cost_passthrough():
    stages = [_pb(100.0), None, _pb(200.0)]
    dag = fuse_breakdowns(stages, deps=[(), (0,), (1,)])
    assert dag.compute == 300.0          # the unmodeled node adds nothing


def test_fuse_dag_validation():
    stages = [_pb(100.0), _pb(200.0)]
    with pytest.raises(ValueError):
        fuse_breakdowns(stages, deps=[()])           # misaligned
    with pytest.raises(ValueError):
        fuse_breakdowns(stages, deps=[(), (1,)])     # self/forward dep
    with pytest.raises(ValueError):
        fuse_breakdowns([None, None], deps=[(), ()])  # nothing modeled


# ---------------------------------------------------------------------------
# Satellite fixes: drain watermark, released-event wait, wait-list checks
# ---------------------------------------------------------------------------
def test_drain_starts_at_watermark(monkeypatch):
    """Repeated partial drains must wait each event ONCE — O(new work),
    not O(history)."""
    ctx = _ctx()
    q = CommandQueue(ctx)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    for _ in range(4):
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    waited = []
    orig = Event.wait
    monkeypatch.setattr(Event, "wait",
                        lambda self: (waited.append(self), orig(self))[1])
    q.drain(2)
    assert len(waited) == 2
    waited.clear()
    q.drain(4)                           # must wait ONLY events 2 and 3
    assert len(waited) == 2
    waited.clear()
    q.drain(4)                           # idempotent on a drained prefix
    assert waited == []


def test_wait_on_released_event_raises():
    ctx = _ctx()
    q = CommandQueue(ctx, profile=False)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    ev = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    q.finish()                           # unprofiled: auto-release sweep
    assert ev.released
    with pytest.raises(RuntimeError):
        ev.wait()                        # use-after-release is loud


def test_wait_events_validation():
    ctx = _ctx()
    q = CommandQueue(ctx, profile=False)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    done = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    q.finish()                           # releases `done`
    with pytest.raises(RuntimeError):
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a), wait_events=[done])
    with pytest.raises(TypeError):
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a), wait_events=["ev"])
    # a capture-time event cannot order an eager launch
    q2 = CommandQueue(ctx, profile=False)
    with q2.capture():
        cap_ev = q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    with pytest.raises(RuntimeError):
        q.enqueue_nd_range(_mm_kernel(), NDR, (a, a), wait_events=[cap_ev])
    # ...and an eager event cannot order a captured node
    live = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    with pytest.raises(RuntimeError):
        with q2.capture():
            q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a),
                                wait_events=[live])


def test_eager_marker_keeps_in_order_chain_and_rejects_capture_events():
    ctx = _ctx()
    q = CommandQueue(ctx, profile=False)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    e0 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    other = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    # explicit wait list on an in-order queue: the marker is still chained
    # after everything previously enqueued (clEnqueueMarkerWithWaitList)
    m = q.enqueue_marker(wait_events=[other])
    e1 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    e1.wait()
    assert e0.done                       # the chain ran through the marker
    # a capture-time event cannot order an eager marker/barrier
    q2 = CommandQueue(ctx, profile=False)
    with q2.capture():
        cap_ev = q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    with pytest.raises(RuntimeError):
        q.enqueue_marker(wait_events=[cap_ev])
    with pytest.raises(RuntimeError):
        q.enqueue_barrier(wait_events=[cap_ev])
    q.finish()


def test_in_order_eager_event_chains_implicitly():
    ctx = _ctx()
    q = CommandQueue(ctx, profile=False)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    e1 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    e2 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    assert e1 in e2.deps                 # implicit in-order edge
    e2.wait()
    assert e1.done and e2.deps == ()     # realized: chain refs dropped
    # out-of-order: no implicit edge
    q2 = CommandQueue(ctx, profile=False, out_of_order=True)
    f1 = q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    f2 = q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    assert f1 not in f2.deps
    q2.finish()


def test_eager_dataflow_is_an_ordering_edge():
    """Consuming another launch's output buffer is a dependency edge even
    on an out-of-order queue — wait() realizes the producer transitively,
    mirroring what capture records via slot producers."""
    ctx = _ctx()
    q = CommandQueue(ctx, profile=False, out_of_order=True)
    a = ctx.create_buffer(jnp.ones((8, 8), jnp.float32))
    e0 = q.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    e1 = q.enqueue_nd_range(_mm_kernel(), NDR, e0.outputs + (a,))
    assert e0 in e1.deps
    e1.wait()
    assert e0.done
    # cross-queue dataflow too (in-order chains don't cover a foreign queue)
    q2 = CommandQueue(ctx, profile=False)
    f0 = q2.enqueue_nd_range(_mm_kernel(), NDR, (a, a))
    g0 = q.enqueue_nd_range(_mm_kernel(), NDR, f0.outputs + (a,))
    assert f0 in g0.deps
    q.finish()
    q2.finish()


# ---------------------------------------------------------------------------
# Satellite fix: MicroBatch.crop per-output true lengths
# ---------------------------------------------------------------------------
def test_crop_uses_per_output_lengths():
    """Multi-input pipelines with differing extents: output j crops to
    input j's true length, not lengths[0]."""
    b = BucketBatcher((8,), max_batch=1)
    b.submit(jnp.arange(5, dtype=jnp.float32),
             jnp.arange(7, dtype=jnp.float32))   # both pad to bucket 8
    (mb,) = b.drain()
    assert mb.requests[0].lengths == (5, 7)
    outs = [mb.inputs[0] * 2, mb.inputs[1] * 3]
    (o0, o1) = mb.crop(outs)[0]
    assert o0.shape == (5,)
    assert o1.shape == (7,)              # was wrongly cropped to 5
    # host arrays, bit-identical to the device rows they were cut from
    assert isinstance(o0, np.ndarray) and isinstance(o1, np.ndarray)
    assert o0.tobytes() == np.asarray(outs[0][0, :5]).tobytes()
    assert o1.tobytes() == np.asarray(outs[1][0, :7]).tobytes()
    np.testing.assert_array_equal(np.asarray(o1),
                                  3 * np.arange(7, dtype=np.float32))


def test_crop_per_output_padded_extent_detection():
    """Arrays landing in DIFFERENT buckets: the padded-extent check is
    per output too, so a secondary output matching ITS OWN bucket size is
    cropped correctly."""
    b = BucketBatcher((8, 16), max_batch=1)
    b.submit(jnp.arange(5, dtype=jnp.float32),     # -> bucket 8
             jnp.arange(12, dtype=jnp.float32))    # -> bucket 16
    (mb,) = b.drain()
    outs = [mb.inputs[0] * 2, mb.inputs[1] * 3]
    (o0, o1) = mb.crop(outs)[0]
    assert o0.shape == (5,)
    assert o1.shape == (12,)             # was returned whole (16,) before
    assert isinstance(o0, np.ndarray) and isinstance(o1, np.ndarray)
    assert o1.tobytes() == np.asarray(outs[1][0, :12]).tobytes()
