"""repro.obs — end-to-end observability: spans, traces, metrics.

Spans run on two clocks:

* the **device clock** — :func:`span` writes a profiler span
  (``jax.profiler.TraceAnnotation``) at each layer boundary of the served
  path (``server.``, ``engine.``, ``graph.``, ``batch.``, ``dispatch.``).
  A profiler trace holds them beside the chip's own operations, on the
  same clock, so it shows what the host was doing while the device idled;
  with no profiler session they record nothing (:mod:`.profiler`);
* the **modeled clock** — the e-GPU machine model knows where every
  modeled nanosecond of a pipeline request goes, and :class:`Tracer`
  makes that inspectable.

Four pieces:

* :func:`span` — the profiler spans above;
* :class:`Tracer` / :class:`Span` — per-request span trees of the
  pipeline path, with explicit parent links, recorded on the *modeled
  virtual clock* (the same injected clock + per-lane
  ``modeled_busy_until`` discipline as the goodput gates), so traces are
  deterministic and assertable;
* :meth:`Tracer.to_chrome_json` — a Perfetto/Chrome-trace exporter:
  request trees and per-lane launch slices (sized by each node's captured
  :class:`~repro.core.machine.PhaseBreakdown`, laid out along the DAG
  critical path so concurrent branches visibly overlap);
* :class:`MetricsRegistry` with :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` — the unified telemetry registry the serving
  counters publish into, dumping as :meth:`MetricsRegistry.snapshot` or
  Prometheus text.

Modeled tracing is opt-in and zero-overhead-when-off: ``Server(tracer=...)`` and
``CommandQueue(tracer=...)`` take a tracer explicitly, every hook guards
on ``tracer is not None``, and telemetry never perturbs modeled totals,
goodput, or outputs (the traced benchmark arms assert bit-identity).

Worked example — tracing one request from submit to result::

    import jax.numpy as jnp
    from repro.core import EGPU_16T, Kernel, Stage
    from repro.obs import Tracer
    from repro.serve import Server

    class VClock:                      # the bench-style virtual clock
        t = 0.0
        def __call__(self):
            return self.t

    k = Kernel("scale", executor=lambda x: (x * 2.0,))
    clk, tracer = VClock(), Tracer()
    srv = Server([Stage(k, n_inputs=1)], workers=(EGPU_16T,),
                 bucket_sizes=(8,), max_batch=1, clock=clk, tracer=tracer)
    rid = srv.submit(jnp.ones((4, 4)))          # max_batch=1: launches now
    srv.flush()
    (out,) = srv.result(rid)

    root = tracer.request_root(rid)             # the rid's span tree:
    for s in tracer.children(root):             #   admission   [t0, t0]
        print(s.name, s.t0, s.t1)               #   bucket-wait [t0, t_launch]
                                                #   dispatch    [t_launch, t_x]
                                                #   execute     [t_x, t_done]
                                                #   result      [t_done, t_done]
    assert tracer.validate_request_trees() == []
    tracer.to_chrome_json("trace.json")         # open in ui.perfetto.dev

The serving stack emits spans at every hop — submit, admission,
bucket-wait, deadline-flush, dispatch-pick, retry/backoff, launch,
per-stage kernel+transfer execution, retire, result — with fault
injections, breaker trips, shed decisions, and cache hits/misses attached
as span events.  :meth:`Tracer.validate_request_trees` pins the
completeness contract: every accepted rid's tree closes with exactly one
terminal span (``result`` or a named ``shed``).
"""

from .metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry)
from .profiler import span
from .trace import (TERMINAL_SPANS, Span, Tracer, validate_chrome_trace)

__all__ = [
    "Counter",
    "DEFAULT_BUCKETS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Span",
    "TERMINAL_SPANS",
    "Tracer",
    "span",
    "validate_chrome_trace",
]
