"""The paper's TinyBio pipeline served through ``Server.submit`` /
``result`` over one ``QueueWorker`` lane.

Clients each keep one recording in flight (a closed loop).  Recordings are
drawn from the seed into a pool at set-up; request ``i`` sends the pool's
item the traffic stream names.  The check compares every answer the
window returned with ``chipbench.reference.tinybio`` on its recording.
"""

from __future__ import annotations

import collections
import gc
import math
from typing import Dict, List

import jax
import numpy as np

from ..bench import traffic
from ..bench.lmserve import Calls
from ..reference import tinybio as reference

F32 = 4


def recordings(cfg: dict, seed: int, count: int) -> np.ndarray:
    """``count`` respiration-like recordings (breathing at 0.15-0.4 Hz, a
    slower swing, drift and noise) sampled at 32 Hz."""
    rng = np.random.default_rng([seed, 11])
    t = np.arange(cfg["n"]) / 32.0
    f1 = rng.uniform(0.15, 0.4, (count, 1))
    f2 = rng.uniform(0.03, 0.1, (count, 1))
    ph = rng.uniform(0, 2 * np.pi, (count, 2))
    x = (np.sin(2 * np.pi * f1 * t + ph[:, :1])
         + 0.3 * np.sin(2 * np.pi * f2 * t + ph[:, 1:])
         + 0.1 * rng.standard_normal((count, cfg["n"])))
    return x.astype(np.float32)


# -- operations and bytes, from shapes ---------------------------------------
def kernel_costs(cfg: dict) -> Dict[str, tuple]:
    """(flops, bytes) of each kernel for ONE recording: the arithmetic the
    stage needs and the bytes it must read and write once."""
    n, taps = cfg["n"], cfg["taps"]
    win, nw = cfg["win"], cfg["n_windows"]
    nsv, nf = cfg["n_sv"], cfg["n_features"]
    stages = int(math.log2(win))
    return {
        "fir": (2.0 * n * taps, (2 * n + taps) * F32),
        # three compares and two ands per flag side, one subtract
        "delineate": (11.0 * n, n * F32 + n),
        # radix-2: n/2 butterflies of 10 real flops per stage; re and im
        # planes read and written
        "stockham_fft": (nw * 5.0 * win * stages, nw * win * F32 * 4),
        # distances as a product, norms, exp and the weighted sum
        "svm": (2.0 * nw * nsv * nf + 6.0 * nw * nsv,
                (nw * nf + nsv * (nf + 1) + nw) * F32),
    }


def pipeline_flops(cfg: dict) -> float:
    return sum(f for f, _ in kernel_costs(cfg).values())


class Bench:
    def __init__(self, cell, seed: int, calls: Calls):
        self.cell, self.seed, self.calls = cell, seed, calls
        self.cfg = cell.config
        self.serve = cell.serve
        self.mix = cell.traffic
        self.sent: List[tuple] = []          # (index, item, rid, t_submit)
        self.answers: Dict[int, np.ndarray] = {}
        self.done_at: Dict[int, float] = {}

    def setup(self) -> None:
        from repro.apps.tinybio import TINYBIO_WORKLOAD, tinybio_stages
        from repro.core import EGPU_16T
        from repro.serve import QueueWorker, Server

        for k, v in TINYBIO_WORKLOAD.items():
            if self.cfg[k] != v:
                raise ValueError(f"the program serves {k}={v}, the "
                                 f"configuration states {self.cfg[k]}")
        self.const_seed = self.seed % 2 ** 32
        stages, _ = tinybio_stages(EGPU_16T, self.const_seed)
        self.lane = QueueWorker(EGPU_16T, name="tinybio")
        self.server = Server(stages, workers=(self.lane,),
                             bucket_sizes=(self.cfg["n"],),
                             max_batch=self.serve["max_batch"])
        self.pool_np = recordings(self.cfg, self.seed, self.mix["pool"])
        self.pool = [jax.device_put(x) for x in self.pool_np]
        # one full and one partial batch: every program the window runs
        for k in (self.serve["max_batch"], 1):
            rids = [self.server.submit(self.pool[i % len(self.pool)])
                    for i in range(k)]
            self.server.flush()
            for r in rids:
                np.asarray(self.server.result(r)[0])

    def launches(self) -> int:
        return self.lane.n_batches

    def rows(self) -> int:
        """Requests the lane's launches carried (padding rows not counted)."""
        return self.lane.n_requests

    def window(self, t0: float, seconds: float, on_tick) -> None:
        stream = traffic.closed_stream(self.mix, self.seed, len(self.pool))
        end = t0 + seconds
        pending: "collections.deque[tuple]" = collections.deque()
        clients = self.mix["clients"]
        server, calls = self.server, self.calls

        def send():
            r = next(stream)
            with calls.span("submit") as call:
                rid = server.submit(self.pool[r.item])
            self.sent.append((r.index, r.item, rid, call.t0))
            pending.append(rid)

        def collect(n: int) -> int:
            """Fetch the ``n`` answers that completed, oldest first."""
            got = 0
            for rid in list(pending):
                if got >= n:
                    break
                try:
                    with calls.span("result") as call:
                        out = np.asarray(server.result(rid)[0])
                except KeyError:
                    continue
                self.answers[rid] = out
                self.done_at[rid] = call.t1
                pending.remove(rid)
                got += 1
            return got

        done = server.n_completed
        while True:
            now = calls.clock()
            on_tick(now)
            if now < end:
                while len(pending) < clients:
                    send()
            elif not pending:
                break
            new = server.n_completed - done
            if new == 0:
                with calls.span("flush"):
                    server.flush()
                new = server.n_completed - done
                if new == 0:
                    break                # the rest never come: the check
            done += collect(new)         # counts them as missing

    def completions(self) -> List[float]:
        return list(self.done_at.values())

    def attempted(self) -> int:
        return len(self.sent)

    def failed(self) -> int:
        return sum(rid not in self.answers for _, _, rid, _ in self.sent)

    def lateness_p95(self) -> None:
        return None                     # a closed loop is never late

    def release(self) -> None:
        del self.server, self.lane, self.pool
        gc.collect()

    def check(self, control: bool = False) -> dict:
        consts = reference.constants(self.cfg, self.const_seed)
        items = sorted({item for _, item, _, _ in self.sent})
        want = {i: reference.pipeline(self.pool_np[i], self.cfg, consts)
                for i in items}
        err = 0.0
        for _, item, rid, _ in self.sent:
            got, ref = self.answers.get(rid), want[item]
            if got is None:
                continue
            if got.shape != ref.shape:
                err = float("inf")
                continue
            err = max(err, float(np.max(np.abs(got - ref))
                                 / np.max(np.abs(ref))))
        missing = sum(rid not in self.answers for _, _, rid, _ in self.sent)
        limits = self.serve["limits"]
        out = {"missing": (float(missing), 0.0),
               "answer_err": (err, limits["answer_err"])}
        if control:
            low = 0.0
            for i in items:
                got = reference.pipeline(self.pool_np[i], self.cfg, consts,
                                         dtype="bfloat16")
                low = max(low, float(np.max(np.abs(got - want[i]))
                                     / np.max(np.abs(want[i]))))
            out["control_answer_err"] = (low, limits["answer_err"])
        return out
