"""Drives a ``Server`` with a decode engine through its public front:
``submit_decode`` and ``stream``.

The server runs one engine step whenever a stream that has no token
queued is advanced.  The driver keeps the streams in lockstep: after every
call it reads each request's queued tokens (which never steps the engine),
then advances one stream that has none, which runs exactly one step for
every occupied slot.  It mirrors the server's slot admission from the
engine's public prefill counter (``n_prefills``), so it knows at every
moment which requests hold a slot, and when each token reached the client.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Call:
    """One call into the server, as the client saw it."""

    id: int
    name: str
    t0: float = 0.0
    t1: float = 0.0
    prefills: List[int] = dataclasses.field(default_factory=list)
    step: bool = False          # ran one engine decode step
    live: List[int] = dataclasses.field(default_factory=list)  # kv lengths


@dataclasses.dataclass
class Served:
    """One request and what the client received."""

    index: int
    prompt: np.ndarray
    max_new: int
    due: float                  # clock time it was due (open) / sent (closed)
    rid: int = -1
    t_submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    result: Optional[np.ndarray] = None


class Calls:
    """The harness's record of every call, with a trace annotation around
    each one while a trace is being taken."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.calls: List[Call] = []
        self.annotate = None        # jax.profiler.TraceAnnotation while tracing

    @contextlib.contextmanager
    def span(self, name: str):
        call = Call(id=len(self.calls), name=name)
        self.calls.append(call)
        ann = (self.annotate(name, id=call.id) if self.annotate is not None
               else contextlib.nullcontext())
        with ann:
            call.t0 = self.clock()
            yield call
            call.t1 = self.clock()


class LMDriver:
    def __init__(self, server, engine, calls: Calls):
        self.server = server
        self.engine = engine
        self.calls = calls
        self.clock = calls.clock
        self.active: "collections.OrderedDict[int, Served]" = \
            collections.OrderedDict()
        self.waiting: "collections.deque[Served]" = collections.deque()
        self.ready: Dict[int, int] = {}
        self.streams: Dict[int, Iterator[int]] = {}
        self.by_rid: Dict[int, Served] = {}
        self.served: List[Served] = []
        self.finished_now: List[Served] = []
        #: called with the clock between calls (the harness starts and
        #: stops its trace there); returns the seconds it held the loop,
        #: which the open loop's schedule then skips
        self.on_tick: Callable[[float], Optional[float]] = lambda now: None

    # -- one call each ------------------------------------------------------
    def submit(self, req: Served) -> None:
        n0 = self.engine.n_prefills
        with self.calls.span("submit") as call:
            req.t_submit = call.t0
            req.rid = self.server.submit_decode(req.prompt, req.max_new)
        self.served.append(req)
        self.streams[req.rid] = self.server.stream(req.rid)
        self.by_rid[req.rid] = req
        admitted = self.engine.n_prefills - n0
        if admitted == 1 and not self.waiting:
            self._admit(req, call)
        elif admitted == 0:
            self.waiting.append(req)
        else:
            raise AssertionError(f"submit_decode ran {admitted} prefills "
                                 f"with {len(self.waiting)} waiting")
        self._read()

    def advance(self) -> None:
        """One engine step for every occupied slot."""
        driver = next(iter(self.active.values()))
        live = [len(r.prompt) + len(r.times) for r in self.active.values()]
        n0, s0 = self.engine.n_prefills, self.engine.n_steps
        with self.calls.span("advance") as call:
            tok = next(self.streams[driver.rid])
        if self.engine.n_steps != s0 + 1:
            raise AssertionError(f"one advance ran "
                                 f"{self.engine.n_steps - s0} engine steps")
        call.step, call.live = True, live
        for r in self.active.values():
            r.times.append(call.t1)
            self.ready[r.rid] += 1
        self.ready[driver.rid] -= 1
        driver.tokens.append(int(tok))
        self.finished_now = [r for r in self.active.values()
                             if len(r.times) >= r.max_new]
        for r in self.finished_now:
            del self.active[r.rid]
        for _ in range(self.engine.n_prefills - n0):
            self._admit(self.waiting.popleft(), call)
        self._read()

    def _admit(self, req: Served, call: Call) -> None:
        call.prefills.append(len(req.prompt))
        req.times.append(call.t1)
        self.active[req.rid] = req
        self.ready[req.rid] = 1

    def _read(self) -> None:
        """Take every queued token off its stream; a finished request's
        stream then ends and its result is fetched."""
        for rid, n in list(self.ready.items()):
            it, req = self.streams[rid], self.by_rid[rid]
            for _ in range(n):
                req.tokens.append(int(next(it)))
            self.ready[rid] = 0
            if rid not in self.active and len(req.tokens) >= req.max_new:
                if next(it, None) is not None:
                    raise AssertionError(f"request {rid} streamed more than "
                                         f"{req.max_new} tokens")
                with self.calls.span("result"):
                    req.result = np.asarray(self.server.result(rid)[0])
                del self.ready[rid], self.streams[rid], self.by_rid[rid]

    # -- loops ----------------------------------------------------------------
    def open_loop(self, requests: List[Served]) -> None:
        """Send each request when it is due; step while any slot is busy."""
        i = 0
        while True:
            now = self.clock()
            held = self.on_tick(now)
            if held:
                # stopping a trace holds the host for many seconds: every
                # request not yet answered keeps its place in the schedule,
                # as if the clock had stopped with it
                for r in list(self.waiting) + requests[i:]:
                    r.due += held
            while i < len(requests) and requests[i].due <= now:
                self.submit(requests[i])
                i += 1
                now = self.clock()
            if self.active:
                self.advance()
            elif i >= len(requests):
                break
            else:
                time.sleep(max(0.0, requests[i].due - self.clock()))

    def closed_loop(self, next_request: Callable[[float], Optional[Served]],
                    clients: int) -> None:
        """``clients`` clients, each sending its next request as soon as
        its last one is answered, while ``next_request`` gives one."""
        for _ in range(clients):
            req = next_request(self.clock())
            if req is not None:
                self.submit(req)
        while self.active:
            self.on_tick(self.clock())
            self.advance()
            for _ in self.finished_now:
                req = next_request(self.clock())
                if req is not None:
                    self.submit(req)
