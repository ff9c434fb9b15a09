"""Runs one cell once: set-up, a measured window, a check, one result line.

Everything a cell needs is found by name:

* ``BENCHMARK.json`` names the cell's configuration, traffic mix and chips,
  and which metrics it reports;
* ``chipbench/configs/<config>.json`` holds the configuration, whose
  ``family`` names the module ``chipbench/families/<family>.py`` that
  builds and drives it;
* ``chipbench/traffic/<mix>.json`` holds the mix's parameters;
* ``chipbench/workloads/<cell>.json`` holds the cell's serving settings
  (slots, lengths, batch, the offered rate) and the limits of its check;
* ``chipbench/metrics/<metric>.py`` reads one metric from the run.

So a cell, a mix, a configuration of a known family or a metric is added
by adding files (and the cell's entry in ``BENCHMARK.json``).
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
import traceback
from typing import Callable, List, Optional

from . import trace as tracemod
from .device import CompileClock, DeviceError, check_device, load_peaks
from .lmserve import Calls

ROOT = pathlib.Path(__file__).resolve().parents[2]
#: where runs keep what they write: the trace of a traced run
OUT_DIR = ".chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    serve: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = json.loads((root / conf["file"]).read_text())
    config["name"] = conf["name"]
    bdir = root / "chipbench"
    return Cell(
        name=name, chips=int(entry["chips"]), config=config,
        traffic=json.loads(
            (bdir / "traffic" / f"{entry['traffic']}.json").read_text()),
        serve=json.loads((bdir / "workloads" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def load_metric(name: str, root: pathlib.Path = ROOT):
    """The reader module ``chipbench/metrics/<name>.py``."""
    path = root / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str):
    return importlib.import_module(f"chipbench.families.{name}")


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: Cell
    seed: int
    seconds: float
    peaks: dict
    setup_s: float
    t0: float                      # window opens (host clock)
    bench: object                  # the family's Bench, after its window
    family: object                 # its module: operations and bytes
    trace: Optional[tracemod.Trace] = None
    #: calls inside the traced window, each with its span on the trace clock
    traced: List[tuple] = dataclasses.field(default_factory=list)
    launches_traced: int = 0       # launches counted by the lane's stats
    rows_traced: int = 0           # requests those launches carried
    answers_traced: int = 0        # requests answered inside the window

    @property
    def peak_flops(self) -> float:
        return self.peaks["bf16_flops_per_s"] * self.cell.chips

    @property
    def peak_bytes(self) -> float:
        return self.peaks["hbm_bytes_per_s"] * self.cell.chips


class TracePlan:
    """Takes one profiler trace of ``[start, stop)`` on the host clock,
    started and stopped from the window's loop."""

    def __init__(self, jax, calls: Calls, bench, out: pathlib.Path,
                 start: float, stop: float):
        self.jax, self.calls, self.bench = jax, calls, bench
        self.out, self.start, self.stop = out, start, stop
        self.state = 0
        self.first_call = self.last_call = 0
        self.launches = self.rows = self.answers = 0
        self.stop_s = None             # how long stopping the trace took

    def _count(self):
        b = self.bench
        return (b.launches() if hasattr(b, "launches") else 0,
                b.rows() if hasattr(b, "rows") else 0,
                len(b.completions()))

    def tick(self, now: float) -> float:
        """Starts or stops the trace when it is time; returns the seconds
        the stop held the host (0 otherwise)."""
        if self.state == 0 and now >= self.start:
            shutil.rmtree(self.out, ignore_errors=True)
            # the harness's own spans and the device: the Python tracer,
            # the runtime's own host events and the programs' HLO would
            # make stopping the trace block the window for a minute
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(str(self.out),
                                          profiler_options=opts)
            self.window = self.jax.profiler.TraceAnnotation(
                tracemod.WINDOW_SPAN)
            self.window.__enter__()
            self.calls.annotate = self.jax.profiler.TraceAnnotation
            self.first_call = len(self.calls.calls)
            self.launches, self.rows, self.answers = self._count()
            self.state = 1
        elif self.state == 1 and now >= self.stop:
            self.end()
            return self.stop_s
        return 0.0

    def end(self) -> None:
        if self.state != 1:
            return
        self.calls.annotate = None
        self.last_call = len(self.calls.calls)
        launches, rows, answers = self._count()
        self.launches, self.rows, self.answers = (
            launches - self.launches, rows - self.rows,
            answers - self.answers)
        self.window.__exit__(None, None, None)
        t = time.perf_counter()
        self.jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        self.state = 2


def trace_bounds(seconds: float) -> tuple:
    """(offset, length) of the traced part of a window: past the first
    quarter, at most six seconds."""
    return seconds / 4, min(seconds / 2, 6.0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: pathlib.Path = ROOT, require_chip: bool = True,
        device_kind: Optional[str] = None, control: bool = False,
        prepare: Optional[Callable] = None,
        rate: Optional[float] = None) -> dict:
    """One run of ``cell``; returns the result record (see ``main``).

    ``require_chip=False`` (tests) skips the look for a TPU and takes the
    peaks of ``device_kind``; ``prepare(bench)`` may change the built
    system before its window (tests plant faults there); ``rate``
    overrides an open-loop cell's offered rate (the knee sweep).

    ``control`` puts the control in the program's place: each number the
    family reads for its control (``control_<name>``) is judged as
    ``<name>``, so ``correct`` must come out false, and the program's own
    reading is kept beside it as ``program_<name>``."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    clock = CompileClock(jax)
    if require_chip:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        device = check_device(jax, cell.chips)
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": device_kind,
                  "count": cell.chips}
    peaks = load_peaks(device["kind"])
    if rate is not None:
        cell = dataclasses.replace(cell, serve=dict(cell.serve, rate=rate))
    calls = Calls()
    family = load_family(cell.config["family"])
    bench = family.Bench(cell, seed, calls)
    bench.setup()
    if prepare is not None:
        prepare(bench)
    compiles0, compile_s0 = clock.compiles, clock.compile_s
    out = root / OUT_DIR / "trace"
    t0 = time.perf_counter()
    plan = None
    if trace:
        off, length = trace_bounds(seconds)
        plan = TracePlan(jax, calls, bench, out, t0 + off, t0 + off + length)
    bench.window(t0, seconds, plan.tick if plan else (lambda now: None))
    if plan is not None:
        plan.end()
    window_compiles = clock.compiles - compiles0
    window_compile_s = clock.compile_s - compile_s0
    used = jax.devices()[: cell.chips]
    stats = [d.memory_stats() or {} for d in used]
    device["memory_peak_bytes"] = max(
        int(s.get("peak_bytes_in_use", 0)) for s in stats)
    r = Run(cell=cell, seed=seed, seconds=seconds, peaks=peaks,
            setup_s=t0 - t_start, t0=t0, bench=bench, family=family)
    if plan is not None and plan.state == 2:
        r.trace = tracemod.load(str(out), [d.id for d in used])
        spans = {s.id: s for s in tracemod.spans_in_window(r.trace)}
        r.traced = [(c, spans[c.id])
                    for c in calls.calls[plan.first_call: plan.last_call]
                    if c.id in spans]
        r.launches_traced, r.rows_traced = plan.launches, plan.rows
        r.answers_traced = plan.answers
        shutil.rmtree(out, ignore_errors=True)
        device["busy_s"] = r.trace.busy_ns(*r.trace.window) / 1e9
        device["window_s"] = r.trace.window_ns() / 1e9
    done = bench.completions()
    drain_s = max(done) - (t0 + seconds) if done else None
    bench.release()
    compared = bench.check(control=control)
    if control:
        for key in [k for k in compared if k.startswith("control_")]:
            name = key[len("control_"):]
            compared["program_" + name] = compared[name]
            compared[name] = compared.pop(key)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_metric(m["name"], root).read(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for v, lim in compared.values()),
        "attempted": bench.attempted(),
        "failed": bench.failed(),
        "metrics": metrics,
        "device": device,
    }
    if r.trace is not None:
        result["breakdown"] = {"device_ops": r.trace.top_ops(),
                               "idle_gaps": r.trace.idle_gaps()}
    result["window"] = {"compiles": window_compiles,
                        "compile_s": window_compile_s,
                        "late_s_p95": bench.lateness_p95(),
                        "drain_s": drain_s,
                        "trace_stop_s": plan.stop_s if plan else None}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def main(argv: List[str], t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program source under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        cell = load_cell(args.workload)
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=t_start)
    except DeviceError as e:
        print(e, file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
    return 0


def _finite(x):
    """``x`` with every float that is not finite written as null."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x
