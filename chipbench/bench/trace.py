"""From a profiler trace to the numbers the per-layer metrics read.

A traced run wraps every call it makes into the system in a
``jax.profiler.TraceAnnotation`` named after the call (``submit``,
``advance``, ``flush``, ``result``) with an ``id`` that joins it to the
harness's own record of the call, and wraps the whole traced period in one
annotation named ``traced``.  The reduction keeps those host spans and the
device operations of the cell's own chips, all on the profiler's clock, and
computes from them: the union of device-busy intervals, busy time inside
spans, kernel time by name, the top operations, and idle gaps labelled by
the span the host was in.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Tuple

SPAN_NAMES = ("submit", "advance", "flush", "result")
WINDOW_SPAN = "traced"
#: the line of a TPU plane that holds one event per device operation
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    id: int
    t0: int          # ns, profiler clock
    t1: int


@dataclasses.dataclass(slots=True)
class Op:
    device: int
    name: str
    t0: int
    t1: int


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    spans: List[Span]
    ops: List[Op]
    n_devices: int

    # -- storage (the recorded trace the tests read) ------------------------
    def to_json(self) -> dict:
        return {"window": list(self.window), "n_devices": self.n_devices,
                "spans": [dataclasses.astuple(s) for s in self.spans],
                "ops": [dataclasses.astuple(o) for o in self.ops]}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(window=tuple(d["window"]), n_devices=d["n_devices"],
                   spans=[Span(*s) for s in d["spans"]],
                   ops=[Op(*o) for o in d["ops"]])

    # -- reductions ---------------------------------------------------------
    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        """Merged intervals in which an operation ran on ``device``."""
        cache = self.__dict__.setdefault("_busy", {})
        if device not in cache:
            iv = sorted((o.t0, o.t1) for o in self.ops if o.device == device)
            merged: List[List[int]] = []
            for a, b in iv:
                if merged and a <= merged[-1][1]:
                    merged[-1][1] = max(merged[-1][1], b)
                else:
                    merged.append([a, b])
            cache[device] = [tuple(m) for m in merged]
        return cache[device]

    def busy_ns(self, t0: int, t1: int) -> float:
        """Device-busy ns inside [t0, t1], averaged over the devices."""
        total = 0
        for d in range(self.n_devices):
            iv = self.busy_intervals(d)
            i = max(0, bisect.bisect_right(iv, (t0, t0)) - 1)
            while i < len(iv) and iv[i][0] < t1:
                a, b = iv[i]
                total += max(0, min(b, t1) - max(a, t0))
                i += 1
        return total / max(1, self.n_devices)

    def op_ns(self, t0: int, t1: int, pattern: str) -> float:
        """Summed device ns of operations whose name matches ``pattern``
        and that start inside [t0, t1], averaged over the devices."""
        rx = re.compile(pattern)
        total = sum(o.t1 - o.t0 for o in self.ops
                    if t0 <= o.t0 < t1 and rx.search(o.name))
        return total / max(1, self.n_devices)

    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operation names with most device time, seconds summed
        over the window and averaged over the devices.  Loops are left
        out: their time is that of the operations inside them."""
        by: Dict[str, int] = {}
        for o in self.ops:
            name = re.sub(r"\.\d+$", "", o.name)
            if name == "while":
                continue
            by[name] = by.get(name, 0) + (o.t1 - o.t0)
        top = sorted(by.items(), key=lambda kv: -kv[1])[:k]
        return [[n, ns / max(1, self.n_devices) / 1e9] for n, ns in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps between device operations (device 0),
        each named by the host span its midpoint fell in, or ``host`` where
        the harness was between calls."""
        w0, w1 = self.window
        iv = [(max(a, w0), min(b, w1)) for a, b in self.busy_intervals(0)
              if b > w0 and a < w1]
        edges = [w0] + [x for ab in iv for x in ab] + [w1]
        gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(reverse=True)
        spans = sorted(self.spans, key=lambda s: s.t0)
        starts = [s.t0 for s in spans]
        out = []
        for ns, a, b in gaps[:k]:
            mid = (a + b) // 2
            j = bisect.bisect_right(starts, mid) - 1
            label = (spans[j].name if j >= 0 and spans[j].t1 >= mid
                     else "host")
            out.append([label, ns / 1e9])
        return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def load(trace_dir: str, device_ids: Iterable[int]) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``, keeping the TPU
    planes of ``device_ids`` (the chips the cell uses) and no other."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    spans: List[Span] = []
    ops: List[Op] = []
    window = None
    devices = []
    wanted = set(device_ids)
    for plane in data.planes:
        tpu = re.match(r"/device:TPU:(\d+)", plane.name)
        if tpu:
            if int(tpu.group(1)) in wanted:
                devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (int(ev.start_ns), int(ev.end_ns))
                    elif ev.name in SPAN_NAMES:
                        sid = _stats(ev).get("id")
                        if sid is not None:
                            spans.append(Span(ev.name, int(sid),
                                              int(ev.start_ns),
                                              int(ev.end_ns)))
    for d, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                # "%fusion.12 = bf16[...] fusion(...)": keep "fusion.12"
                name = ev.name.split(" = ", 1)[0].lstrip("%")
                ops.append(Op(d, name, int(ev.start_ns), int(ev.end_ns)))
    if window is None:
        raise ValueError(f"the trace under {trace_dir} has no "
                         f"{WINDOW_SPAN!r} span")
    return Trace(window=window, spans=spans, ops=ops,
                 n_devices=max(1, len(wanted)))


def save_json(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def spans_in_window(trace: Trace, names: Iterable[str] = SPAN_NAMES
                    ) -> List[Span]:
    w0, w1 = trace.window
    names = set(names)
    return [s for s in trace.spans
            if s.name in names and s.t0 >= w0 and s.t1 <= w1]
