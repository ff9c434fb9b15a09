"""The reduction from a trace to busy time, kernel time, top operations and
labelled idle gaps, on a small hand-made trace and on a small trace
recorded on a TPU v5e (``data/trace_small.json.gz``)."""

import json
import pathlib

import pytest

import benchtree
from chipbench.bench import layers
from chipbench.bench import trace as tm

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _hand():
    spans = [tm.Span("advance", 1, 100, 400), tm.Span("submit", 2, 500, 900)]
    ops = [tm.Op(0, "while.3", 150, 300),
           tm.Op(0, "fusion.1", 150, 250), tm.Op(0, "fusion.2", 200, 300),
           tm.Op(0, "flash_kernel", 600, 700), tm.Op(1, "fusion.1", 0, 1000)]
    return tm.Trace(window=(0, 1000), spans=spans, ops=ops, n_devices=2)


def test_busy_is_the_union_averaged_over_devices():
    t = _hand()
    assert t.busy_intervals(0) == [(150, 300), (600, 700)]
    assert t.busy_ns(0, 1000) == (250 + 1000) / 2
    assert t.busy_ns(100, 400) == (150 + 300) / 2
    assert t.op_ns(500, 900, "flash") == 100 / 2


def test_top_ops_and_labelled_gaps():
    t = _hand()
    assert t.top_ops(2) == [["fusion", 1200 / 2 / 1e9],
                            ["flash_kernel", 100 / 2 / 1e9]]
    gaps = t.idle_gaps(3)
    # device 0 idles over [0, 150] (before any call), [300, 600] (between
    # calls) and [700, 1000] (inside the submit)
    assert gaps == [["submit", 300 / 1e9], ["host", 300 / 1e9],
                    ["host", 150 / 1e9]]


def test_round_trip_through_json(tmp_path):
    t = _hand()
    tm.save_json(t, str(tmp_path / "t.json.gz"))
    u = tm.load_json(str(tmp_path / "t.json.gz"))
    assert u.to_json() == t.to_json()


@pytest.mark.skipif(not (DATA / "trace_small.json.gz").exists(),
                    reason="no recorded trace")
def test_recorded_chip_trace():
    t = tm.load_json(str(DATA / "trace_small.json.gz"))
    w0, w1 = t.window
    busy = t.busy_ns(w0, w1)
    assert 0 < busy <= w1 - w0
    spans = tm.spans_in_window(t)
    assert spans and all(s.name in tm.SPAN_NAMES for s in spans)
    inside = sum(t.busy_ns(s.t0, s.t1) for s in spans)
    assert inside <= busy + 1
    assert t.top_ops(10) and t.idle_gaps(10)
    assert all(label in tm.SPAN_NAMES + ("host",)
               for label, _ in t.idle_gaps(10))
    # every TinyBio kernel pattern of the configuration finds its events
    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "tinybio.json").read_text())
    for pattern in cfg["kernels"].values():
        assert t.op_ns(w0, w1, pattern) > 0, pattern


class _Run:
    def __init__(self, trace, traced):
        self.trace, self.traced = trace, traced


def test_layer_selections():
    from chipbench.bench.lmserve import Call

    t = _hand()
    step = Call(1, "advance", step=True, live=[5])
    pre = Call(2, "submit", prefills=[16])
    run = _Run(t, [(step, t.spans[0]), (pre, t.spans[1])])
    assert layers.decode_only(run) == [(step, t.spans[0])]
    assert layers.prefilling(run) == [(pre, t.spans[1])]
    assert layers.idle_share(run) == pytest.approx(100 * (1 - 625 / 1000))


def _plane(name, lines):
    from types import SimpleNamespace as NS

    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e, start_ns=a, end_ns=b, stats=st)
                            for e, a, b, st in evs])
        for ln, evs in lines])


def test_load_keeps_only_the_cells_chips(tmp_path, monkeypatch):
    """A one-chip cell on a host of four reads device 0 alone: the idle
    chips neither dilute busy time nor count as devices."""
    import jax.profiler

    (tmp_path / "h.xplane.pb").write_bytes(b"")
    host = _plane("/host:CPU", [("python", [
        (tm.WINDOW_SPAN, 0, 1000, ()), ("advance", 100, 400, [("id", 7)])])])
    planes = [host] + [
        _plane(f"/device:TPU:{d}", [(tm.OPS_LINE, [
            ("%fusion.1 = bf16[8] fusion(x)", 100, 300, ())])])
        for d in range(1)] + [
        _plane(f"/device:TPU:{d}", [(tm.OPS_LINE, [])]) for d in (1, 2, 3)]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: type("P", (), {
                            "planes": planes})()))
    t = tm.load(str(tmp_path), [0])
    assert t.n_devices == 1
    assert t.busy_ns(0, 1000) == 200
    assert [o.name for o in t.ops] == ["fusion.1"]
    assert [(s.name, s.id) for s in t.spans] == [("advance", 7)]
    assert tm.load(str(tmp_path), [0, 1, 2, 3]).busy_ns(0, 1000) == 50


@pytest.mark.skipif(not (DATA / "trace_small.json.gz").exists(),
                    reason="no recorded trace")
def test_tinybio_roofline_counts_the_rows_launched():
    """The kernels' least time is that of the requests the lane launched,
    not of full batches: half the rows, half the share."""
    from types import SimpleNamespace as NS

    from chipbench.bench import harness
    from chipbench.bench.device import load_peaks
    from chipbench.families import tinybio

    t = tm.load_json(str(DATA / "trace_small.json.gz"))
    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "tinybio.json").read_text())
    reader = harness.load_metric("tinybio_roofline")

    def share(rows):
        return reader.read(NS(trace=t, rows_traced=rows, family=tinybio,
                              peaks=load_peaks("TPU v5 lite"),
                              cell=NS(config=cfg, serve={"max_batch": 16})))
    assert 0 < share(16) < 100
    assert share(8) == pytest.approx(share(16) / 2)
    assert reader.read(NS(trace=t, rows_traced=0)) is None
