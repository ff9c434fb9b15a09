"""The program's spans in a trace (``chipbench.bench.program``): the
readings on a hand-made trace whose answers are worked out by hand, the
loader on a stand-in profile, and the trace readers that existed before
program spans did, pinned on the trace recorded on a TPU v5e."""

import json
import pathlib
from types import SimpleNamespace as NS

import pytest

import benchtree
from chipbench.bench import layers
from chipbench.bench import program as pm
from chipbench.bench import trace as tm

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _trace():
    """Device 0 busy over [100, 200], [300, 400], [700, 800] of a window
    [0, 1000]; the harness in ``advance`` over [50, 450] and in ``submit``
    over [500, 900]."""
    ops = [tm.Op(0, "fusion.1", 100, 200), tm.Op(0, "fusion.2", 300, 400),
           tm.Op(0, "flash", 700, 800)]
    spans = [tm.Span("advance", 1, 50, 450), tm.Span("submit", 2, 500, 900)]
    return tm.Trace(window=(0, 1000), spans=spans, ops=ops, n_devices=1)


def _program():
    S = pm.ProgramSpan
    return pm.Program(spans=[
        S("server.step", 60, 440, {"occupied": 2}),
        S("engine.generate", 70, 430, {"occupied": 2}),
        S("graph.launch", 80, 120, {"graph": "engine.generate", "first": 0}),
        S("engine.readback", 120, 420, {}),
        S("engine.prefill", 455, 470, {"rid": 4, "wait_us": 0.0}),
        S("server.submit_decode", 510, 890, {"rid": 5}),
        S("engine.prefill", 520, 850, {"rid": 5, "wait_us": 2000.0}),
        S("graph.launch", 600, 650, {"graph": "engine.prefill", "first": 0}),
        S("engine.insert", 860, 880, {"slot": 0}),
        S("batch.form", 910, 920, {"n": 16, "capacity": 16}),
        S("dispatch.launch", 920, 940, {"lane": "bio", "n": 16}),
        S("server.finalize", 940, 990, {"tickets": 2, "n": 32}),
        # ends past the window: left out of every mean
        S("engine.prefill", 950, 1200, {"rid": 6, "wait_us": 9000.0}),
    ], modules=[tm.Op(0, "jit_engine.generate(12)", 100, 200),
                tm.Op(0, "jit_engine.prefill(3)", 700, 800),
                tm.Op(0, "jit_engine.generate(12)", 300, 400)])


@pytest.mark.parametrize("name,want", [
    # waits of 0 and 2 ms in the window: linear 95th percentile
    ("slot_wait_p95_ms", 1.9),
    ("insert_host_ms", 20 / 1e6),
    # the launch inside the generate; the prefill's launch is not counted
    ("generate_launch_ms", 40 / 1e6),
    # one launch: 10 ns forming the batch and 20 ns dispatching it
    ("dispatch_host_ms", 30 / 1e6),
    # 50 ns finalizing two launches
    ("finalize_host_ms", 25 / 1e6),
    # open over [60, 440], [455, 470], [510, 890], [910, 1000]: idle
    # 180 + 15 + 280 + 90 of 1000 ns
    ("idle_in_program_share", 56.5),
])
def test_reading_by_hand(name, want):
    assert pm.READINGS[name](_trace(), _program()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(pm.READINGS))
def test_no_program_span_reads_nothing(name):
    assert pm.READINGS[name](_trace(), pm.Program(spans=[], modules=[])) \
        is None


def test_idle_outside_the_program_is_the_rest_of_the_idle_share():
    t, p = _trace(), _program()
    outside = layers.idle_share(NS(trace=t)) - pm.idle_in_program_share(t, p)
    # [0, 60], [440, 455], [470, 510], [890, 910]
    assert outside == pytest.approx(13.5)


def test_gaps_name_the_innermost_span_open():
    assert pm.idle_gaps(_trace(), _program(), 4) == [
        [300 / 1e9, 400 / 1e9, "engine.prefill", "submit"],
        [200 / 1e9, 800 / 1e9, pm.OUTSIDE, "submit"],
        [100 / 1e9, 200 / 1e9, "engine.readback", "advance"],
        [100 / 1e9, 0.0, pm.OUTSIDE, "advance"]]


def test_module_names_drop_the_run_id():
    assert _program().module_names() == ["jit_engine.generate",
                                         "jit_engine.prefill"]


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e, start_ns=a, end_ns=b, stats=st)
                            for e, a, b, st in evs])
        for ln, evs in lines])


def test_load_keeps_program_spans_and_the_cells_modules(tmp_path,
                                                        monkeypatch):
    import jax.profiler

    (tmp_path / "h.xplane.pb").write_bytes(b"")
    host = _plane("/host:CPU", [("python", [
        (tm.WINDOW_SPAN, 0, 1000, ()), ("advance", 100, 400, [("id", 7)]),
        ("engine.generate", 150, 390, [("occupied", 3)]),
        ("graph.launch", 160, 200, [("graph", "engine.generate"),
                                    ("first", 1)]),
        ("enginex", 10, 20, ())])])
    planes = [host] + [
        _plane(f"/device:TPU:{d}", [
            (pm.MODULES_LINE, [(f"jit_engine.generate({d})", 200, 380, ())]),
            (tm.OPS_LINE, [("%fusion.1 = bf16[8] fusion(x)", 200, 300, ())])])
        for d in (0, 1)]
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: NS(planes=planes)))
    p = pm.load(str(tmp_path), [0])
    assert [(s.name, s.t0, s.t1, s.stats) for s in p.spans] == [
        ("engine.generate", 150, 390, {"occupied": 3}),
        ("graph.launch", 160, 200, {"graph": "engine.generate", "first": 1})]
    assert [(m.device, m.name) for m in p.modules] == [
        (0, "jit_engine.generate(0)")]
    assert len(pm.load(str(tmp_path), [0, 1]).modules) == 2


@pytest.mark.skipif(not (DATA / "trace_small.json.gz").exists(),
                    reason="no recorded trace")
def test_readers_before_program_spans_are_unchanged():
    """The recorded TinyBio trace (no program spans) reads as it did
    before the program wrote any."""
    from chipbench.bench import harness
    from chipbench.bench.device import load_peaks
    from chipbench.families import tinybio

    t = tm.load_json(str(DATA / "trace_small.json.gz"))
    assert t.window == (49569799, 349569799) and t.n_devices == 1
    assert len(tm.spans_in_window(t)) == 174 and len(t.ops) == 738
    assert t.busy_ns(*t.window) == 189387474.0
    assert t.top_ops(3) == [["fir_pallas", 0.171917463],
                            ["fft_pallas", 0.014786734],
                            ["delineate_pallas", 0.001474597]]
    assert t.idle_gaps(4) == [["result", 0.018863988],
                              ["result", 0.01868495],
                              ["result", 0.008685881],
                              ["submit", 0.003801244]]
    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "tinybio.json").read_text())
    peaks = load_peaks("TPU v5 lite")
    run = NS(trace=t, family=tinybio, peaks=peaks, launches_traced=4,
             rows_traced=16, answers_traced=16,
             peak_flops=peaks["bf16_flops_per_s"],
             cell=NS(config=cfg, chips=1, serve={"max_batch": 16}))
    want = {"idle_share.batch": 36.870842, "idle_share.chat": 36.870842,
            "launch_host_ms": 27.6531315,
            "tinybio_roofline": 0.020319697870422277,
            "tinybio_mfu": 0.0006227583350253808}
    for name, value in want.items():
        assert harness.load_metric(name).read(run) == pytest.approx(
            value, rel=1e-12), name
