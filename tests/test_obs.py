"""repro.obs — span tracer, Chrome-trace export, telemetry registry
(ISSUE 7).

Pins the observability contracts: request-tree completeness
(``validate_request_trees``), Chrome trace-event schema validity
(``validate_chrome_trace``), counter/gauge/histogram semantics with label
sets and Prometheus text exposition, the per-request flame decomposition
summing to end-to-end modeled latency, and — the zero-overhead guarantee —
that an untraced server allocates NO object from ``repro.obs`` on its hot
dispatch path while producing the exact same modeled totals and
bit-identical outputs as its traced twin.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (APU, EGPU_16T, CommandQueue, Context, Device,
                        Kernel, NDRange, Stage)
from repro.kernels.gemm.ref import counts as gemm_counts
from repro.kernels.gemm.ref import gemm_ref
from repro.obs import (Gauge, Histogram, MetricsRegistry, Span,
                       TERMINAL_SPANS, Tracer, validate_chrome_trace)
from repro.serve import Server
from repro.serve.server import DECOMP_PHASES

NDR = NDRange((8, 8), (4, 4))


class VClock:
    """Manually-advanced virtual clock for deterministic serve sessions."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mm_stages(d=8, seed=0, n=2):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)

    def mlp(x, w):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    kern = Kernel("mlp", executor=mlp,
                  counts=lambda **kw: gemm_counts(m=d, n=d, k=d))
    return [Stage(kern, consts=(w,), n_inputs=1) for _ in range(n)]


def _traced_session(n=6, tracer=None, clk=None):
    clk = clk or VClock()
    stages = _mm_stages()
    srv = Server(stages, workers=(EGPU_16T,), bucket_sizes=(8,),
                 max_batch=2, clock=clk, tracer=tracer)
    rng = np.random.default_rng(3)
    rids = []
    for i in range(n):
        clk.t = 0.01 * i
        x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
        rids.append((srv.submit(x), x))
    clk.t = 0.01 * n + 0.1
    srv.flush()
    return srv, stages, rids


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------
def test_span_basics_and_explicit_parent_links():
    tr = Tracer()
    root = tr.begin("request", 1.0, track="rid:7", rid=7, priority=0)
    child = tr.span("execute", 1.5, 2.5, track="rid:7", parent=root, rid=7)
    tr.event(root, 2.0, "retry", lane="0:x")
    assert root.open and not child.open
    assert child.parent_id == root.span_id
    assert child.duration_s == pytest.approx(1.0)
    assert tr.children(root) == [child]
    tr.end(root, 3.0)
    assert root.duration_s == pytest.approx(2.0)
    with pytest.raises(RuntimeError, match="already ended"):
        tr.end(root, 4.0)
    with pytest.raises(ValueError, match="before start"):
        tr.span("bad", 2.0, 1.0)


def test_request_tree_lifecycle_and_validation():
    tr = Tracer()
    tr.begin_request(0, 0.0, priority=1)
    tr.request_event(0, 0.5, "dispatch-pick", lane="0:x")
    tr.child(0, "bucket-wait", 0.0, 0.5)
    tr.finish_request(0, 1.0, "result")
    assert tr.validate_request_trees() == []
    root = tr.request_root(0)
    names = [s.name for s in tr.children(root)]
    assert "admission" in names and names.count("result") == 1
    # events on a finished rid are silently dropped (late bookkeeping)
    tr.request_event(0, 2.0, "retry")
    assert not any(n == "retry" for (_, n, _) in root.events)
    # double-open is loud; double-finish is idempotent-safe
    with pytest.raises(RuntimeError, match="already has a root"):
        tr.begin_request(0, 0.0)
    assert tr.finish_request(0, 9.9, "result") is None
    with pytest.raises(ValueError, match="terminal"):
        tr.finish_request(1, 0.0, "oops")


def test_validator_flags_dangling_and_multi_terminal_trees():
    tr = Tracer()
    tr.begin_request(3, 0.0)
    errs = tr.validate_request_trees()
    assert any("dangling" in e for e in errs)
    assert any("terminal" in e for e in errs)
    # a shed terminal closes it cleanly
    tr.finish_request(3, 0.4, "shed", reason="deadline")
    assert tr.validate_request_trees() == []
    assert set(TERMINAL_SPANS) == {"result", "shed"}


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------
def test_chrome_export_schema_and_track_layout(tmp_path):
    tr = Tracer()
    tr.begin_request(2, 0.0)
    tr.child(2, "execute", 0.2, 0.9)
    tr.finish_request(2, 1.0, "result")
    tr.span("launch", 0.1, 0.9, track="lane:0:e-gpu-16t", n_requests=2)
    tr.instant("lane:0:e-gpu-16t", 1.0, "retire", n_requests=2)
    tr.instant("server", 0.05, "shed-at-door", reason="queue-full")
    path = tmp_path / "trace.json"
    doc = tr.to_chrome_json(path)
    assert validate_chrome_trace(doc) == []
    assert path.exists()
    import json
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {(e["name"], e["args"]["name"]) for e in meta}
    assert ("process_name", "requests") in names
    assert ("process_name", "lanes") in names
    assert ("thread_name", "rid:2") in names
    # rid tracks keep the rid as tid, under the requests pid
    rid_rows = [e for e in evs if e.get("cat") == "rid:2" and e["ph"] == "X"]
    assert rid_rows and all(e["pid"] == 1 and e["tid"] == 2
                            for e in rid_rows)
    # ts/dur are microseconds of virtual time
    execute = next(e for e in rid_rows if e["name"] == "execute")
    assert execute["ts"] == pytest.approx(0.2e6)
    assert execute["dur"] == pytest.approx(0.7e6)


def test_chrome_validator_catches_orphans_and_non_monotonic_ts():
    assert validate_chrome_trace({}) != []
    assert validate_chrome_trace({"traceEvents": 3}) != []
    bad_orphan = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0, "dur": 1.0,
         "args": {"span_id": 1, "parent_id": 999}}]}
    assert any("orphan" in e for e in validate_chrome_trace(bad_orphan))
    bad_ts = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 5.0, "dur": 1.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 4.0, "dur": 1.0}]}
    assert any("monotonic" in e for e in validate_chrome_trace(bad_ts))
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0, "dur": -1.0}]}
    assert any("negative dur" in e for e in validate_chrome_trace(bad_dur))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_counter_inc_set_total_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help text")
    c.inc(lane="a")
    c.inc(2.0, lane="a")
    c.inc(lane="b")
    assert c.value(lane="a") == 3.0 and c.value(lane="b") == 1.0
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1.0)
    # snapshot-publisher style: idempotent, loud on decrease
    c.set_total(5.0, lane="a")
    c.set_total(5.0, lane="a")
    assert c.value(lane="a") == 5.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.set_total(4.0, lane="a")
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name!")
    with pytest.raises(ValueError, match="invalid label name"):
        c.inc(**{"bad-label": 1})


def test_gauge_and_histogram_semantics():
    g = Gauge("g")
    g.set(2.5, lane="x")
    g.inc(0.5, lane="x")
    assert g.value(lane="x") == 3.0
    h = Histogram("h", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.005, 0.005, 0.05, 0.5):
        h.observe(v)
    snap = h.value()
    assert snap["count"] == 5 and snap["sum"] == pytest.approx(0.5605)
    assert snap["buckets"][0.01] == 3          # cumulative
    assert h.quantile(0.5) == 0.01             # bucket upper bound
    assert h.quantile(1.0) == 0.5              # clamped to observed max
    with pytest.raises(ValueError):
        Histogram("h2", buckets=())


def test_registry_get_or_create_and_type_clash():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    assert reg.get("x_total") is a and reg.get("nope") is None


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("c_total", "a counter").set_total(3, lane="a")
    reg.gauge("g").set(1.5)
    h = reg.histogram("h", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05, lane="z")
    txt = reg.to_prometheus_text()
    assert "# HELP c_total a counter" in txt
    assert "# TYPE c_total counter" in txt
    assert 'c_total{lane="a"} 3.0' in txt
    assert "g 1.5" in txt
    assert 'h_bucket{lane="z",le="0.1"} 1' in txt
    assert 'h_bucket{lane="z",le="+Inf"} 1' in txt
    assert 'h_sum{lane="z"} 0.05' in txt
    assert 'h_count{lane="z"} 1' in txt
    snap = reg.snapshot()
    assert snap["c_total"]["type"] == "counter"
    assert snap["c_total"]["samples"][0]["labels"] == {"lane": "a"}


# ---------------------------------------------------------------------------
# Traced server end-to-end
# ---------------------------------------------------------------------------
def test_traced_server_emits_complete_request_trees():
    tr = Tracer()
    srv, stages, rids = _traced_session(tracer=tr)
    assert sorted(rid for rid, _ in rids) == tr.request_rids()
    assert tr.validate_request_trees() == []
    for rid, _ in rids:
        root = tr.request_root(rid)
        names = [s.name for s in tr.children(root)]
        for expected in ("admission", "bucket-wait", "dispatch", "execute",
                         "result"):
            assert expected in names, (rid, names)
        evs = [n for (_, n, _) in root.events]
        assert "submit" in evs and "dispatch-pick" in evs
        # phase children tile [t_submit, t_done] contiguously
        by = {s.name: s for s in tr.children(root)}
        assert by["bucket-wait"].t0 == root.t0
        assert by["dispatch"].t0 == by["bucket-wait"].t1
        assert by["execute"].t0 == by["dispatch"].t1
        assert by["execute"].t1 == root.t1
    # the first micro-batch misses the graph cache, later ones hit
    all_evs = [n for rid, _ in rids
               for (_, n, _) in tr.request_root(rid).events]
    assert "cache-miss" in all_evs and "cache-hit" in all_evs
    # lane track: one launch slice per batch, with kernel slices under it
    launches = [s for s in tr.spans if s.name == "launch"]
    assert len(launches) == 3            # 6 requests / max_batch 2
    kid_names = {s.name for launch in launches
                 for s in tr.children(launch)}
    assert "startup+scheduling" in kid_names and "mlp" in kid_names
    doc = srv.tracer.to_chrome_json()
    assert validate_chrome_trace(doc) == []


def test_traced_and_untraced_twins_agree_bit_identically():
    srv_t, stages, rids_t = _traced_session(tracer=Tracer())
    srv_u, _, rids_u = _traced_session(tracer=None)
    rt, ru = srv_t.report(), srv_u.report()
    assert rt.n_requests == ru.n_requests
    assert rt.modeled_latency_s == ru.modeled_latency_s
    assert rt.goodput_per_s_modeled == ru.goodput_per_s_modeled
    for (rid_t, x), (rid_u, _) in zip(rids_t, rids_u):
        (a,) = srv_t.result(rid_t)
        (b,) = srv_u.result(rid_u)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ref, _ = APU(EGPU_16T).offload(stages, (x,), mode="eager")
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(ref[0].data))


def test_untraced_server_allocates_no_obs_objects(monkeypatch):
    """The zero-overhead-when-off guarantee: with tracer=None the hot
    path must never construct a Span (or any tracer state)."""
    def boom(*a, **kw):
        raise AssertionError("repro.obs.Span allocated on untraced path")

    monkeypatch.setattr(Span, "__init__", boom)
    srv, stages, rids = _traced_session(tracer=None)
    for rid, _ in rids:
        assert len(srv.result(rid)) == 1
    assert srv.report().n_requests == len(rids)


def test_flame_decomposition_sums_to_end_to_end_latency():
    tr = Tracer()
    srv, _, rids = _traced_session(tracer=tr)
    rep = srv.report()
    decomp = rep.latency_decomposition_s
    assert set(decomp) == set(DECOMP_PHASES)
    for phase, pcts in decomp.items():
        assert set(pcts) == {50, 99}
    # per-request: the five phase children of each tree tile submit->done,
    # so summing the phase series must reproduce the end-to-end latency
    for rid, _ in rids:
        root = tr.request_root(rid)
        by = {s.name: s for s in tr.children(root)}
        phases = (by["admission"].duration_s + by["bucket-wait"].duration_s
                  + by["dispatch"].duration_s + by["execute"].duration_s)
        assert phases == pytest.approx(root.t1 - root.t0)
    lines = rep.summary().splitlines()
    flame = [ln for ln in lines if ln.startswith("flame")]
    assert len(flame) == 2
    assert all(phase in flame[0] for phase in DECOMP_PHASES)


def test_server_publish_metrics_covers_the_stack():
    srv, _, rids = _traced_session(tracer=None)
    reg = srv.publish_metrics()
    assert isinstance(reg, MetricsRegistry)
    c = reg.get("repro_serve_requests_total")
    assert c is not None and c.value() == len(rids)
    assert reg.get("repro_graph_cache_events_total").value(kind="misses") == 1
    lane = reg.get("repro_lane_requests_total")
    assert lane is not None
    (key,) = lane.labels()
    assert dict(key)["lane"] == "0:e-gpu-16t"
    # idempotent re-publish into the same registry (snapshot style)
    assert srv.publish_metrics(reg) is reg
    assert c.value() == len(rids)
    txt = reg.to_prometheus_text()
    assert "repro_serve_latency_phase_seconds" in txt
    assert 'quantile="p50"' in txt


# ---------------------------------------------------------------------------
# CommandQueue tracing + released-event metadata (satellite)
# ---------------------------------------------------------------------------
def _mm_kernel(d=8, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((d, d)) * 0.2, jnp.float32)

    def mlp(x):
        return jnp.maximum(gemm_ref(x, w), 0.0)

    return Kernel("mlp", executor=mlp,
                  counts=lambda **kw: gemm_counts(m=d, n=d, k=d))


def test_command_queue_traces_modeled_kernel_spans():
    tr = Tracer()
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx, tracer=tr)
    x = jnp.ones((8, 8), jnp.float32)
    e1 = q.enqueue_nd_range(_mm_kernel(), NDR, (ctx.create_buffer(x),))
    e2 = q.enqueue_nd_range(_mm_kernel(seed=1), NDR, (e1.outputs[0],))
    q.finish()
    spans = [s for s in tr.spans if s.track.startswith("queue:")]
    assert [s.name for s in spans] == ["mlp", "mlp"]
    # laid end-to-end on the queue's cumulative modeled timeline
    assert spans[0].t0 == 0.0
    assert spans[0].duration_s == pytest.approx(e1.modeled.total_s)
    assert spans[1].t0 == pytest.approx(e1.modeled.total_s)
    assert spans[1].duration_s == pytest.approx(e2.modeled.total_s)
    assert validate_chrome_trace(tr.to_chrome_json()) == []


def test_released_event_metadata_survives_profiling_window():
    """Pins the released-event contract ``Event.wall_s`` documents: release
    drops the functional outputs (wait() is loud) while the O(1) cost
    metadata — dispatch_s/wall_s, modeled, energy_j — stays readable."""
    ctx = Context(Device(EGPU_16T))
    q = CommandQueue(ctx, max_events=1)   # bounded profiling window
    x = jnp.ones((8, 8), jnp.float32)
    kern = _mm_kernel()
    events = [q.enqueue_nd_range(kern, NDR, (ctx.create_buffer(x),))
              for _ in range(3)]
    q.finish()
    released = [e for e in events if e.released]
    assert len(released) == 2            # window kept only the newest
    for ev in released:
        assert ev.wall_s == ev.dispatch_s >= 0.0
        assert ev.modeled is not None and ev.modeled.total_s > 0.0
        assert ev.energy_j is not None and ev.energy_j > 0.0
        assert ev.outputs == ()
        with pytest.raises(RuntimeError, match="released"):
            ev.wait()
    # window totals stay exact regardless of the release
    assert q.total_modeled_s() == pytest.approx(
        sum(e.modeled.total_s for e in events))
