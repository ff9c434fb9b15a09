"""repro.serve — the APU serving subsystem (ISSUE 2).

Turns the one-shot ``APU.offload`` pipeline into a long-lived serving
engine:

* :class:`GraphCache` — memoized compiled :class:`CommandGraph`\\ s across
  offloads (LRU, hit/miss/eviction counters);
* :class:`BucketBatcher` — shape-bucketed dynamic batching (pad-to-bucket,
  coalesce, crop back);
* :class:`MultiQueueDispatcher` / :class:`QueueWorker` — load-balanced
  multi-queue dispatch with in-flight-depth backpressure and per-queue
  machine-model accounting;
* :class:`ShardedWorker` (ISSUE 5) — a dispatcher lane spanning a
  :class:`jax.sharding.Mesh` slice: cached graphs are lowered with
  ``NamedSharding``\\ s derived from the ``repro.distributed`` rule table
  (batch -> data axes, divisibility fallback to replication), modeled
  totals scale by the shard count;
* :class:`Server` / :class:`ServeReport` — the front-end tying them
  together: submit -> batch -> cached fused launch -> per-request results +
  requests/s, modeled latency percentiles and per-mesh-axis utilization;
* the open-loop front door (ISSUE 6) — SLO-aware intake
  (``Server.submit(deadline=..., priority=...)`` with modeled-capacity
  admission control and loud :class:`AdmissionError` sheds), deadline-aware
  partial-bucket flushing, and fault-tolerant dispatch: a deterministic
  seeded :class:`FaultPlan` (launch failures, latency spikes, lane
  :class:`Blackout`\\ s) injected at the worker launch gate, retried by the
  dispatcher onto other lanes with capped backoff, repeat offenders
  quarantined behind :class:`CircuitBreaker`\\ s with half-open probes —
  retried batches stay bit-identical to the fault-free path;
* observability (ISSUE 7) — built with ``Server(tracer=...)`` the whole
  request lifecycle lands in per-rid span trees on the modeled virtual
  clock (see :mod:`repro.obs`), ``Server.publish_metrics`` dumps the
  stack's telemetry into a :class:`~repro.obs.MetricsRegistry`, and
  :attr:`ServeReport.latency_decomposition_s` carries the p50/p99 flame
  attribution over :data:`DECOMP_PHASES`.  All opt-in: an untraced server
  allocates nothing from ``repro.obs`` on its hot dispatch path;
* the continuous-batching decode engine (ISSUE 9) —
  :class:`DecodeEngine` serves autoregressive decode maxtext/JetStream
  style: per-request ``prefill`` -> ``insert`` into a slot of a persistent
  batched decode state resident on the engine's lane -> ``generate``
  advancing ALL occupied slots one token per step in exactly ONE cached
  ``CommandGraph`` launch (slot insertion is a donated-buffer update,
  never a re-capture), bit-identical to whole-batch greedy decoding for
  every cache family under staggered arrival.  ``Server(engine=...)``
  opens the streaming front (``submit_decode`` / per-rid ``stream``
  iterators — a finished request never blocks neighbors), and
  :mod:`repro.serve.http` puts a dependency-free asyncio streaming HTTP
  ingress in front of it.  Engine classes load lazily — pipeline-only
  servers keep the model stack off their import path.
"""

from .batching import (BucketBatcher, MicroBatch, ServeRequest,
                       batched_stages, pad_to)
from .cache import (GraphCache, input_signature, stage_signature,
                    stages_signature)
from .dispatch import (CircuitBreaker, DispatchError, LaunchTicket,
                       MultiQueueDispatcher, QueueStats, QueueWorker)
from .faults import (Blackout, FaultDecision, FaultPlan, InjectedFault,
                     apply_spike, env_seed)
from .server import (DECOMP_PERCENTILES, DECOMP_PHASES, PERCENTILES,
                     AdmissionError, Server, ServeReport)
from .sharded import (BATCH_AXIS, ShardedWorker, data_mesh, mesh_signature,
                      shard_breakdown)

#: engine symbols resolved lazily (PEP 562): importing them pulls the model
#: stack (repro.models / repro.train), which pipeline-only servers avoid
_ENGINE_EXPORTS = ("DecodeEngine", "DecodeState", "Prefix", "batch_axes")
_HTTP_EXPORTS = ("EngineHTTPServer",)


def __getattr__(name: str):
    if name in _ENGINE_EXPORTS:
        from . import engine
        return getattr(engine, name)
    if name in _HTTP_EXPORTS:
        from . import http
        return getattr(http, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "BucketBatcher", "MicroBatch", "ServeRequest", "batched_stages", "pad_to",
    "GraphCache", "input_signature", "stage_signature", "stages_signature",
    "CircuitBreaker", "DispatchError", "LaunchTicket", "MultiQueueDispatcher",
    "QueueStats", "QueueWorker",
    "Blackout", "FaultDecision", "FaultPlan", "InjectedFault", "apply_spike",
    "env_seed",
    "DECOMP_PERCENTILES", "DECOMP_PHASES", "PERCENTILES",
    "AdmissionError", "Server", "ServeReport",
    "BATCH_AXIS", "ShardedWorker", "data_mesh", "mesh_signature",
    "shard_breakdown",
    *_ENGINE_EXPORTS, *_HTTP_EXPORTS,
]
