"""The program's own spans in a profiler trace, and what they show.

The served path writes a profiler span at each layer boundary
(``repro.obs.span``), named after its layer: ``server.``, ``engine.``,
``graph.``, ``batch.``, ``dispatch.``.  They sit in the trace's host plane
on the clock of the device planes, nested as the calls were.  ``load``
reads them, with the "XLA Modules" line of the cell's chips (one event per
program run, named ``jit_<graph name>(<id>)``), from the directory a
traced run wrote.  The readings below reduce them, with the device
operations of a ``chipbench.bench.trace.Trace`` of the same run, to
per-layer numbers; each is ``None`` where the trace holds no program span.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

from .trace import Op, Trace
from .traffic import quantile

#: a program span's name: its layer, a dot, what it times
PROGRAM = re.compile(r"^(server|engine|graph|batch|dispatch)\.")
#: the line of a TPU plane that holds one event per program run
MODULES_LINE = "XLA Modules"
#: the label of an idle gap that no program span covers
OUTSIDE = "outside the program"


@dataclasses.dataclass(slots=True)
class ProgramSpan:
    name: str
    t0: int          # ns, profiler clock
    t1: int
    stats: dict


@dataclasses.dataclass
class Program:
    spans: List[ProgramSpan]
    modules: List[Op]

    def __post_init__(self):
        # by start, an enclosing span before those it holds
        self.spans.sort(key=lambda s: (s.t0, -s.t1))

    def within(self, window: Tuple[int, int], name: Optional[str] = None
               ) -> List[ProgramSpan]:
        """Spans (named ``name``, if given) that lie inside ``window``."""
        w0, w1 = window
        return [s for s in self.spans
                if s.t0 >= w0 and s.t1 <= w1
                and (name is None or s.name == name)]

    def module_names(self) -> List[str]:
        """The distinct programs the chips ran, ``(<id>)`` left out."""
        return sorted({re.sub(r"\(\d+\)$", "", m.name)
                       for m in self.modules})


def load(trace_dir: str, device_ids: Iterable[int]) -> Program:
    """Read the newest ``.xplane.pb`` under ``trace_dir``: every host event
    whose name starts with a program layer, and the module events of the
    TPU planes of ``device_ids``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    spans: List[ProgramSpan] = []
    modules: List[Op] = []
    wanted = set(device_ids)
    devices = []
    for plane in data.planes:
        tpu = re.match(r"/device:TPU:(\d+)", plane.name)
        if tpu:
            if int(tpu.group(1)) in wanted:
                devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if PROGRAM.match(ev.name):
                        spans.append(ProgramSpan(
                            ev.name, int(ev.start_ns), int(ev.end_ns),
                            dict(ev.stats)))
    for d, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules += [Op(d, ev.name, int(ev.start_ns), int(ev.end_ns))
                            for ev in line.events]
    return Program(spans=spans, modules=modules)


def _mean_ms(spans: List[ProgramSpan]) -> Optional[float]:
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) / 1e6


def slot_wait_p95_ms(trace: Trace, prog: Program) -> Optional[float]:
    """95th percentile of the time the requests prefilled in the window
    waited for a slot (``engine.prefill``'s ``wait_us``)."""
    waits = [s.stats["wait_us"] / 1e3
             for s in prog.within(trace.window, "engine.prefill")
             if "wait_us" in s.stats]
    return quantile(waits, 0.95) if waits else None


def insert_host_ms(trace: Trace, prog: Program) -> Optional[float]:
    """Mean time of one ``engine.insert``."""
    return _mean_ms(prog.within(trace.window, "engine.insert"))


def generate_launch_ms(trace: Trace, prog: Program) -> Optional[float]:
    """Mean time of the ``graph.launch`` inside an ``engine.generate``."""
    steps = prog.within(trace.window, "engine.generate")
    launches = prog.within(trace.window, "graph.launch")
    return _mean_ms([g for g in launches
                     if any(s.t0 <= g.t0 and g.t1 <= s.t1 for s in steps)])


def dispatch_host_ms(trace: Trace, prog: Program) -> Optional[float]:
    """Batch formation and dispatch (``batch.form`` plus
    ``dispatch.launch``) per launch."""
    launches = prog.within(trace.window, "dispatch.launch")
    if not launches:
        return None
    forms = prog.within(trace.window, "batch.form")
    ns = sum(s.t1 - s.t0 for s in forms + launches)
    return ns / len(launches) / 1e6


def finalize_host_ms(trace: Trace, prog: Program) -> Optional[float]:
    """``server.finalize`` time per launch it retired."""
    spans = prog.within(trace.window, "server.finalize")
    tickets = sum(s.stats.get("tickets", 0) for s in spans)
    if tickets <= 0:
        return None
    return sum(s.t1 - s.t0 for s in spans) / tickets / 1e6


def _open(trace: Trace, prog: Program) -> List[Tuple[int, int]]:
    """Merged intervals of the window in which a program span is open."""
    w0, w1 = trace.window
    merged: List[List[int]] = []
    for s in prog.spans:
        a, b = max(s.t0, w0), min(s.t1, w1)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def idle_in_program_share(trace: Trace, prog: Program) -> Optional[float]:
    """Percent of the window in which the device is idle (mean over the
    cell's chips) while an outermost program span is open."""
    if not prog.spans or not trace.ops:
        return None
    idle = sum((b - a) - trace.busy_ns(a, b) for a, b in _open(trace, prog))
    return 100.0 * idle / trace.window_ns()


def innermost(prog: Program, t: int) -> Optional[ProgramSpan]:
    """The innermost program span open at ``t``: of those open, the one
    that started last."""
    starts = [s.t0 for s in prog.spans]
    best = None
    for s in prog.spans[:bisect.bisect_right(starts, t)]:
        if s.t1 >= t and (best is None or s.t0 >= best.t0):
            best = s
    return best


def idle_gaps(trace: Trace, prog: Program, k: int = 10) -> List[List]:
    """The ``k`` longest gaps between device operations (device 0), each
    as [seconds, seconds from the window's start, the innermost program
    span open at its midpoint (``OUTSIDE`` where none is), the harness
    call it fell in (``host`` between calls)]."""
    w0, w1 = trace.window
    iv = [(max(a, w0), min(b, w1)) for a, b in trace.busy_intervals(0)
          if b > w0 and a < w1]
    edges = [w0] + [x for ab in iv for x in ab] + [w1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges) - 1, 2)
                   if edges[i + 1] > edges[i]), reverse=True)
    calls = sorted(trace.spans, key=lambda s: s.t0)
    starts = [c.t0 for c in calls]
    out = []
    for ns, a, b in gaps[:k]:
        mid = (a + b) // 2
        span = innermost(prog, mid)
        j = bisect.bisect_right(starts, mid) - 1
        call = calls[j].name if j >= 0 and calls[j].t1 >= mid else "host"
        out.append([ns / 1e9, (a - w0) / 1e9,
                    span.name if span is not None else OUTSIDE, call])
    return out


#: the per-layer readings, by the name each would be reported under
READINGS = {
    "slot_wait_p95_ms": slot_wait_p95_ms,
    "insert_host_ms": insert_host_ms,
    "generate_launch_ms": generate_launch_ms,
    "dispatch_host_ms": dispatch_host_ms,
    "finalize_host_ms": finalize_host_ms,
    "idle_in_program_share": idle_in_program_share,
}
