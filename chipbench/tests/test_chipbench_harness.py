"""The harness end to end on the CPU: small cells added as files only, the
refusal of a CPU device and of an unknown device kind, and the result line
a run prints."""

import json
import pathlib

import pytest

import benchtree
from benchtree import run_cell
from chipbench.bench import harness
from chipbench.bench.device import DeviceError, check_device, load_peaks


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    t = benchtree.make_tree(tmp_path_factory.mktemp("bench"))
    # a per-layer metric added as a file and named in BENCHMARK.json
    (t / "chipbench" / "metrics" / "served_requests.py").write_text(
        'UNIT, BETTER, SOURCE = "requests", "higher", "program_counter"\n'
        'LAYER, MOVES = "engine and server", "itl_p95_ms"\n\n\n'
        'def read(run):\n    return float(run.bench.attempted())\n')
    bench = json.loads((t / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "served_requests", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "engine and server",
        "moves": "itl_p95_ms", "workloads": ["tiny.chat"]})
    (t / "BENCHMARK.json").write_text(json.dumps(bench))
    return t


def test_cpu_device_is_refused():
    import jax

    with pytest.raises(DeviceError, match="needs a TPU"):
        check_device(jax, 1)


def test_unknown_device_kind_is_refused(tmp_path):
    with pytest.raises(DeviceError, match="no peaks"):
        load_peaks("TPU v99")
    assert load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_run_without_the_program_exits_nonzero(tmp_path, monkeypatch,
                                              capsys):
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    rc = harness.main(["--workload", "qwen2.5-3b.chat", "--seed", "1",
                       "--seconds", "1"], 0.0)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_cells_added_as_files_are_found(tree):
    cell = harness.load_cell("tiny.chat", tree)
    assert cell.config["family"] == "qwen2"
    assert cell.serve["slots"] == 4
    assert "served_requests" in [m["name"] for m in cell.per_layer]
    assert harness.load_cell("tinybio.small", tree).chips == 1


def test_lm_cell_reports_its_metrics(tree):
    res = run_cell(tree, "tiny.chat", seed=2 ** 31 + 5)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 30
    # the chat cell judges the gap between tokens end to end; its time to
    # first token is read per layer
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["window"]["compiles"] == 0


def test_traced_run_reports_per_layer_metrics(tree):
    res = run_cell(tree, "tiny.chat", seed=4, trace=True)
    # the added metric is read; on the CPU no TPU plane exists, so the
    # device-trace readers find nothing and are left out
    assert res["metrics"]["served_requests"]["value"] == res["attempted"]
    assert res["metrics"]["ttft_p95_ms.chat"]["value"] > 0
    assert "decode_device_ms" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_trace_stop_pauses_the_open_loop(tree, monkeypatch):
    """Stopping a trace holds the host (over a minute for a chat cell on
    the chip): the open loop's schedule waits with it, so the requests
    after it are neither late nor counted as waiting for the profiler."""
    end = harness.TracePlan.end

    def slow_end(plan):
        end(plan)
        import time
        time.sleep(2.0)
        plan.stop_s += 2.0

    monkeypatch.setattr(harness.TracePlan, "end", slow_end)
    res = run_cell(tree, "tiny.chat", seed=8, trace=True)
    assert res["correct"], res["compared"]
    assert res["window"]["trace_stop_s"] >= 2.0
    assert res["window"]["late_s_p95"] < 1.0
    assert res["metrics"]["ttft_p95_ms.chat"]["value"] < 1000.0


def test_pipeline_cell_reports_its_metrics(tree):
    res = run_cell(tree, "tinybio.small", seed=5, seconds=3.0)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"requests_per_s", "setup_s"}


def test_rate_override_sets_the_offered_load(tree):
    """The knee sweep's override: the same cell at half its rate offers
    half the requests, and the backlog's drain is reported."""
    res = run_cell(tree, "tiny.chat", seed=6, rate=10.0)
    assert res["attempted"] == 15 and res["correct"], res["compared"]
    assert res["window"]["drain_s"] is not None
