"""Sharded serving — one dispatcher lane spanning a device mesh (ISSUE 5).

The paper scales the e-GPU by replicating compute units behind one
Tiny-OpenCL scheduler (§IV, §VI); the serving analogue is a
:class:`ShardedWorker` that owns a :class:`jax.sharding.Mesh` *slice*
instead of a single device.  It is a drop-in
:class:`~repro.serve.dispatch.QueueWorker`: the
:class:`~repro.serve.dispatch.MultiQueueDispatcher` routes micro-batches
across a mix of plain and sharded lanes, and every launch of a cached
:class:`~repro.core.runtime.CommandGraph` is lowered with
``NamedSharding``\\ s derived from the :mod:`repro.distributed.sharding`
rule table:

* the micro-batch leading axis (logical ``"batch"``) spans the mesh's
  data-parallel axes — under the default :data:`SERVE_RULES` that is
  ``("pod", "data")``, pruned to the axes the worker's mesh actually has;
* per-stage constant externals (weights) are replicated unless the worker
  is built with ``const_axes=`` naming their logical axes — a
  model-parallel stage arg tagged ``("heads",)`` lands on ``"model"``;
* the divisibility fallback is preserved end to end: a batch capacity (or
  constant dim) not divisible by its mesh-axis product progressively drops
  trailing axes and replicates if nothing divides, so odd bucket shapes
  degrade gracefully instead of failing to lower.

Contracts:

* **pure compiled code under any binding** — the shardings are a
  launch-time property (``graph.launch_prefix(..., in_shardings=...,
  out_shardings=...)``), never part of the capture, so one cached graph
  carries single-device and sharded executables side by side; the
  :class:`~repro.serve.cache.GraphCache` still keys on the worker's
  :attr:`placement` so sharded and plain entries never collide;
* **honest accounting** — ``batched_stages`` scaled ``WorkCounts`` by the
  batch; a launch that actually splits the batch ``shards`` ways splits
  the chain's transfer + compute across the shards while startup +
  scheduling are still paid (concurrently, once per launch) on every mesh
  slice: :func:`shard_breakdown`.  A fallback-to-replication launch
  reports ``shards == 1`` and scales nothing;
* **bit-identical results** — kernels are pure and batch rows independent,
  so a data-parallel binding cannot change functional outputs (pinned by
  ``tests/test_sharded_serve.py`` on the TinyBio pipeline).
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.device import EGPUConfig
from ..core.machine import PhaseBreakdown
from ..core.runtime import Buffer, CommandGraph
from ..distributed.sharding import ShardingRules, SERVE_RULES, spec_for
from ..obs import Tracer
from .batching import MicroBatch
from .dispatch import QueueStats, QueueWorker
from .faults import FaultPlan, apply_spike

#: logical-axis name of the micro-batch leading dimension
BATCH_AXIS = "batch"


def data_mesh(n_devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """A 1-D data-parallel mesh over the first ``n_devices`` local devices
    (all of them by default) — the common ShardedWorker mesh slice on a
    host whose devices aren't already organized into a grid."""
    devices = jax.devices()
    if n_devices is not None:
        if not 1 <= n_devices <= len(devices):
            raise ValueError(
                f"n_devices must be in 1..{len(devices)}, got {n_devices}")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def mesh_signature(mesh: Mesh) -> Tuple[Any, ...]:
    """Hashable identity of a mesh: axis layout + the concrete devices."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def shard_breakdown(fused: PhaseBreakdown, shards: int) -> PhaseBreakdown:
    """The fused chain's modeled breakdown under ``shards``-way data
    parallelism: transfer + compute split across the shards (each mesh
    slice runs ``1/shards`` of the batch), startup + scheduling paid in
    full (every slice dispatches its shard of the chain concurrently —
    replicating the Tiny-OpenCL scheduler does not shrink its startup)."""
    if shards <= 1:
        return fused
    return dataclasses.replace(
        fused, transfer=fused.transfer / shards,
        compute=fused.compute / shards)


class ShardedWorker(QueueWorker):
    """One serving lane spanning a device-mesh slice.

    ``mesh`` is the worker's slice of the device fleet; ``rules`` the
    logical-axis table used to derive shardings (default
    :data:`~repro.distributed.sharding.SERVE_RULES`).  ``const_axes``
    optionally names the logical axes of each *constant* external (a
    tuple per constant, in capture order, e.g. ``(("heads", None),)`` for
    one model-parallel weight matrix); constants without an entry are
    replicated.  Everything else — backpressure, event-segment retirement,
    per-queue accounting — is inherited from :class:`QueueWorker`; the
    launch path binds every cached-graph replay to the mesh and scales
    the modeled totals by the shard count actually applied.
    """

    def __init__(self, config: EGPUConfig, mesh: Mesh,
                 name: Optional[str] = None, max_in_flight: int = 2,
                 explicit_transfers: bool = True,
                 rules: ShardingRules = SERVE_RULES,
                 const_axes: Optional[Sequence[Optional[Sequence[
                     Optional[str]]]]] = None,
                 fault_plan: Optional[FaultPlan] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 tracer: Optional[Tracer] = None):
        if not isinstance(mesh, Mesh):
            raise TypeError(f"mesh must be a jax.sharding.Mesh, got "
                            f"{type(mesh).__name__}")
        if mesh.devices.size < 1:
            raise ValueError("mesh must hold at least one device")
        self.mesh = mesh
        self.rules = rules
        self.const_axes = (None if const_axes is None else
                           tuple(None if a is None else tuple(a)
                                 for a in const_axes))
        super().__init__(config, name=name, max_in_flight=max_in_flight,
                         explicit_transfers=explicit_transfers,
                         fault_plan=fault_plan, clock=clock, tracer=tracer)
        # Cache identity: sharded captures must never collide with plain
        # single-device ones (or with a different mesh / rule table) in a
        # shared GraphCache.
        self.apu.placement = ("sharded", mesh_signature(mesh), rules.name,
                              self.const_axes)
        #: per-graph derived shardings, keyed weakly so evicted cache
        #: entries do not pin their sharding tuples here
        self._shard_memo: "weakref.WeakKeyDictionary[CommandGraph, Tuple]" = (
            weakref.WeakKeyDictionary())
        # per-axis utilization accumulators (sum of per-launch fractions)
        self._axis_util_sum: Dict[str, float] = {
            str(a): 0.0 for a in mesh.axis_names}
        self._util_launches = 0

    # -- sharding derivation -------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    def _axis_sizes(self) -> Dict[str, int]:
        return {str(a): int(s) for a, s in
                zip(self.mesh.axis_names, self.mesh.devices.shape)}

    def _spec_factor(self, spec: P) -> Dict[str, int]:
        """Per-mesh-axis split factor a PartitionSpec applies."""
        sizes = self._axis_sizes()
        used: Dict[str, int] = {}
        for entry in spec:
            if entry is None:
                continue
            for a in ((entry,) if isinstance(entry, str) else entry):
                used[str(a)] = sizes.get(str(a), 1)
        return used

    def _batch_spec(self, shape: Tuple[int, ...]) -> P:
        """PartitionSpec for a batch-leading tensor (micro-batch inputs and
        outputs): logical ``"batch"`` on dim 0, with the rule table's
        divisibility fallback against the actual extent."""
        logical = (BATCH_AXIS,) + (None,) * (len(shape) - 1)
        return spec_for(logical, self.rules, self.mesh, tuple(shape))

    def shardings_for(self, graph: CommandGraph) -> Tuple[
            Tuple[NamedSharding, ...], Tuple[NamedSharding, ...], int,
            Dict[str, int]]:
        """(in_shardings, out_shardings, batch shard count, axis factors)
        for ``graph``.

        Derived once per graph (memoized weakly): request externals (the
        leading ``graph.n_request_inputs``) and every output span the data
        axes on their batch dim, constant externals follow ``const_axes``
        or replicate.  ``shards`` is the split factor actually applied to
        the batch axis — 1 when the divisibility fallback replicated it.
        ``axis factors`` is the per-mesh-axis split any tensor of the
        launch achieved (batch inputs AND const externals), so
        model-parallel constants register on their axis too.
        """
        memo = self._shard_memo.get(graph)
        if memo is not None:
            return memo
        n_req = getattr(graph, "n_request_inputs", len(graph.ext_avals))
        in_sh = []
        specs = []
        for i, aval in enumerate(graph.ext_avals):
            if i < n_req:
                spec = self._batch_spec(aval.shape)
            else:
                logical = None
                if self.const_axes is not None:
                    j = i - n_req
                    logical = (self.const_axes[j]
                               if j < len(self.const_axes) else None)
                spec = (spec_for(tuple(logical), self.rules, self.mesh,
                                 tuple(aval.shape))
                        if logical is not None else P())
            specs.append(spec)
            in_sh.append(NamedSharding(self.mesh, spec))
        out_specs = [self._batch_spec(aval.shape) for aval in graph.out_avals]
        out_sh = tuple(NamedSharding(self.mesh, s) for s in out_specs)
        batch_factor = self._spec_factor(
            specs[0] if n_req else (out_specs[0] if out_specs else P()))
        shards = 1
        for f in batch_factor.values():
            shards *= f
        # utilization source: the best split ANY tensor achieved per axis —
        # a model-parallel const registers on "model" even though the batch
        # never touches it, so a healthy MP lane is distinguishable from
        # one whose weights silently fell back to replication
        axis_factor: Dict[str, int] = {}
        for spec in list(specs) + out_specs:
            for a, f in self._spec_factor(spec).items():
                axis_factor[a] = max(axis_factor.get(a, 1), f)
        memo = (tuple(in_sh), out_sh, max(1, shards), axis_factor)
        self._shard_memo[graph] = memo
        return memo

    # -- launch --------------------------------------------------------------
    def _do_launch(self, graph: CommandGraph, batch: MicroBatch
                   ) -> Tuple[Tuple[Buffer, ...], Optional[PhaseBreakdown]]:
        # fault gate first — an injected failure fires before any real
        # sharded work, exactly like the plain-lane path
        spike_s = self._fault_gate()
        in_sh, out_sh, shards, axis_factor = self.shardings_for(graph)
        # a pure data-parallel launch (every constant replicated) runs each
        # device's batch slice under shard_map, which Pallas kernels need;
        # model-parallel constants leave the partitioning to GSPMD
        n_req = getattr(graph, "n_request_inputs", len(in_sh))
        per_device = all(sh.spec == P() for sh in in_sh[n_req:])
        outs = graph.launch_prefix(batch.inputs, queue=self.queue,
                                   in_shardings=in_sh, out_shardings=out_sh,
                                   per_device=per_device)
        fused, _ = graph.fused_modeled()
        if fused is not None:
            # transfer + compute split across the mesh slices; startup +
            # scheduling paid once per launch on every slice concurrently
            fused = shard_breakdown(fused, shards)
        fused = apply_spike(fused, spike_s)
        # utilization: fraction of each mesh axis this launch exploited —
        # any tensor's split counts (batch over data, consts over model);
        # fallback-to-replication reads as 1/size
        for a, size in self._axis_sizes().items():
            self._axis_util_sum[a] += axis_factor.get(a, 1) / size
        self._util_launches += 1
        return outs, fused

    def stats(self) -> QueueStats:
        base = super().stats()
        sizes = self._axis_sizes()
        util = tuple(
            (a, self._axis_util_sum[a] / self._util_launches)
            for a in sizes) if self._util_launches else ()
        return dataclasses.replace(
            base, shards=self.n_devices,
            mesh_axes=tuple(sizes.items()), mesh_utilization=util)
