"""TinyCL — the host-side Tiny-OpenCL runtime (paper §V / §VI-C), in JAX.

The paper's runtime is a subset of the OpenCL host API that works without an
OS, file system, or multithreading: create buffers, set kernel args, enqueue
an NDRange, wait for the completion interrupt.  We reproduce that API shape
with JAX semantics:

* a :class:`Buffer` wraps a ``jax.Array`` living in the *unified* memory
  (host HBM == device global memory, exactly the paper's §IV-B model);
* a :class:`Kernel` couples an executor (a pure JAX callable — either the
  pure-jnp reference or the Pallas TPU implementation) with a ``counts``
  function that derives the structural :class:`~repro.core.machine.WorkCounts`
  for the analytic machine model;
* ``CommandQueue.enqueue_nd_range`` jit-executes the kernel and returns an
  :class:`Event` carrying both the functional results and the modeled
  :class:`~repro.core.machine.PhaseBreakdown` / energy for the queue's device
  configuration — the numbers behind Figs 3 & 4;
* events chain: kernels consuming a prior event's outputs execute after it
  (JAX dataflow gives this for free, matching in-order OpenCL queues).

Execution model — asynchronous dispatch (paper §VIII-B)
-------------------------------------------------------

``enqueue_nd_range`` is **non-blocking**: it hands the launch to XLA and
returns immediately with an :class:`Event` whose output buffers hold
*unrealized* ``jax.Array``\\ s.  Back-to-back enqueues therefore overlap
host-side dispatch with device compute — true in-order OpenCL queue
semantics.  Synchronization points are explicit:

* ``Event.wait()`` blocks until that launch (and, by in-order dataflow,
  everything it depends on) completed;
* ``CommandQueue.finish()`` drains the whole queue (``clFinish``);
* ``CommandQueue(..., blocking=True)`` restores the old eager-sync behaviour
  (one host↔device round-trip per launch) for A/B benchmarking.

Execution model — CommandGraph fused dispatch (paper §IV-B)
-----------------------------------------------------------

The paper's TinyBio pipeline chains kernels whose intermediates stay
*resident* in the unified memory; the scheduling cost is paid per launch,
not per byte.  The TPU analogue is whole-chain fusion: ``queue.capture()``
records every ``enqueue_nd_range`` issued inside the ``with`` block —
without executing it (output shapes come from ``jax.eval_shape``) — into a
:class:`CommandGraph`.  ``graph.launch(*inputs)`` then replays the entire
chain as **one** jitted XLA computation: intermediates never materialize as
separate dispatches, XLA reuses their buffers, and optional
``donate_argnums`` donation extends that reuse to the graph's external
inputs.  Dispatch cost is paid once per graph instead of once per kernel.
Per-stage machine-model accounting is preserved: each captured node is
costed from its recorded ``WorkCounts`` at capture time (the captured
schedule), not from wall clock.

Execution model — event-dependency DAGs (ISSUE 3)
-------------------------------------------------

Real OpenCL expresses parallelism through ``event_wait_list`` and
out-of-order queues; TinyCL mirrors both:

* ``CommandQueue(..., out_of_order=True)`` drops the implicit launch-order
  chain — dependencies come only from ``enqueue_nd_range(...,
  wait_events=[...])``, dataflow, and ``enqueue_barrier()`` points;
  ``enqueue_marker()`` (clEnqueueMarkerWithWaitList) aggregates events.
* A capture records these edges per node (``GraphNode.deps``), may span
  *multiple* queues (``graph.join(host_queue)`` — host + e-GPU nodes in one
  graph), and ``fused_modeled()`` reports the DAG's **critical path**:
  concurrent branches overlap in the modeled latency instead of summing.
* ``graph.launch(..., queue=...)`` binds the launch's events and modeled
  totals to the *caller's* queue — a cached graph shared across serving
  workers never books one worker's launch on another's history.

Host API v2 — Program objects and explicit data movement (ISSUE 4)
------------------------------------------------------------------

The host-facing surface mirrors real Tiny-OpenCL object semantics (see
``repro.core.program`` and the ``repro.tinycl`` façade):

* kernels come from a **registry** (``Program.build(config)`` /
  ``program.create_kernel(name)``) and carry clSetKernelArg-style argument
  state (:meth:`Kernel.set_args`, :attr:`Kernel.arg_info`,
  :meth:`CommandQueue.enqueue_kernel`);
* data movement is **first-class**: ``enqueue_write_buffer`` /
  ``enqueue_read_buffer`` / ``enqueue_copy_buffer`` return real events
  costed as transfer-only :class:`PhaseBreakdown`\\ s from the machine
  model's bus parameters, obey queue ordering / ``wait_events`` /
  barriers, and capture as transfer :class:`GraphNode`\\ s — the DAG
  critical path can overlap a branch's traffic with another branch's
  compute instead of hiding it inside each kernel's overlap heuristic;
* :class:`Buffer` flags are enforced: kernels cannot read write-only
  buffers, transfers cannot write read-only ones.

Kernels are executed functionally (outputs are fresh buffers); this is the
one semantic departure from OpenCL's in-place buffer writes and is what makes
every kernel jit/grad/vmap-compatible (the explicit transfer commands are
the only in-place buffer updates, and they replace the whole value).
Out-of-order execution therefore can never change functional results —
ordering is a synchronization and machine-model contract.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import time
import warnings
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..obs.profiler import span
from .device import EGPUConfig, EGPU_16T, HOST
from .machine import (PhaseBreakdown, WorkCounts, egpu_time, fuse_breakdowns,
                      host_time, transfer_time)
from .ndrange import NDRange
from .power import egpu_energy_j, host_energy_j
from .scheduler import optimal_ndrange


#: valid CL_MEM-style access flags: read-only, write-only, read-write
_BUFFER_FLAGS = ("r", "w", "rw")


class Buffer:
    """A unified-memory buffer with **enforced** CL_MEM-style access flags.

    ``flags`` mirror CL_MEM_READ_ONLY / WRITE_ONLY / READ_WRITE and are a
    real contract since the host API v2 redesign: a kernel launch *reads*
    its argument buffers, so passing a write-only (``"w"``) buffer raises;
    :meth:`CommandQueue.enqueue_write_buffer` / ``enqueue_copy_buffer``
    *write* their destination, so a read-only (``"r"``) destination raises.
    Kernels execute functionally (outputs are fresh buffers), so explicit
    transfer commands are the only in-place writes in TinyCL.
    """

    def __init__(self, data: jax.Array, flags: str = "rw"):
        if flags not in _BUFFER_FLAGS:
            raise ValueError(
                f"invalid buffer flags {flags!r}: expected one of "
                f"{_BUFFER_FLAGS} (CL_MEM_READ_ONLY / WRITE_ONLY / "
                "READ_WRITE)")
        self.data = data if isinstance(data, jax.Array) else jnp.asarray(data)
        self.flags = flags

    @property
    def readable(self) -> bool:
        return "r" in self.flags

    @property
    def writable(self) -> bool:
        return "w" in self.flags

    @property
    def nbytes(self) -> int:
        return self.data.size * self.data.dtype.itemsize

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def read(self) -> jax.Array:
        """clEnqueueReadBuffer — a no-op copy under unified memory."""
        return self.data


class GraphBuffer(Buffer):
    """A symbolic buffer produced while capturing a :class:`CommandGraph`.

    Carries only a ``jax.ShapeDtypeStruct`` (shape/dtype/size all work); the
    concrete value exists only inside the fused computation at launch time.
    ``flags`` inherit from the logical source buffer when the node has one
    (transfer commands) instead of hardcoding ``"rw"``, so access control
    survives capture.
    """

    def __init__(self, aval: jax.ShapeDtypeStruct, slot: int,
                 flags: str = "rw"):
        if flags not in _BUFFER_FLAGS:
            raise ValueError(f"invalid buffer flags {flags!r}")
        self.data = aval          # duck-types shape/dtype/size for wiring code
        self.flags = flags
        self.slot = slot

    def read(self) -> jax.Array:
        raise RuntimeError(
            "GraphBuffer holds no data during capture; launch the graph and "
            "read its outputs instead.")


@dataclasses.dataclass(frozen=True)
class ArgInfo:
    """clGetKernelArgInfo analogue: one executor argument's metadata.

    ``kind`` is ``"buffer"`` for required positional arguments (memory
    objects in OpenCL terms) and ``"param"`` for defaulted / keyword-only
    arguments (the kernel-args scalar region).
    """

    index: int
    name: str
    kind: str                       # "buffer" | "param"
    has_default: bool = False


class _ArgState:
    """Mutable clSetKernelArg storage (excluded from Kernel eq/hash)."""

    __slots__ = ("buffers", "params")

    def __init__(self) -> None:
        self.buffers: Optional[List[Optional["Buffer"]]] = None
        self.params: Dict[str, Any] = {}


#: memoized executor introspection: executor -> (arg_info, (min, max) buffer
#: arity).  Weak keys — the cache never outlives an ad-hoc executor; the
#: registry's memoized kernels keep theirs alive anyway.  Executors that
#: reject weakrefs fall through to per-call inspection.
_ARG_INFO_CACHE: "weakref.WeakKeyDictionary[Any, Tuple]" = (
    weakref.WeakKeyDictionary())


def _introspect_executor(executor: Callable[..., Any]) -> Tuple[
        Optional[Tuple["ArgInfo", ...]], Optional[Tuple[int, Optional[int]]]]:
    try:
        cached = _ARG_INFO_CACHE.get(executor)
    except TypeError:
        cached = None
    if cached is not None:
        return cached
    try:
        sig = inspect.signature(executor)
    except (TypeError, ValueError):
        result = (None, None)
    else:
        info: List[ArgInfo] = []
        lo = hi = 0
        variadic = False
        for i, p in enumerate(sig.parameters.values()):
            if p.kind is p.VAR_POSITIONAL:
                info.append(ArgInfo(i, f"*{p.name}", "buffer"))
                variadic = True
            elif p.kind is p.VAR_KEYWORD:
                continue
            elif p.kind is p.KEYWORD_ONLY or p.default is not p.empty:
                info.append(ArgInfo(i, p.name, "param",
                                    has_default=p.default is not p.empty))
            else:
                info.append(ArgInfo(i, p.name, "buffer"))
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
                hi += 1
                if p.default is p.empty:
                    lo += 1
        result = (tuple(info), (lo, None) if variadic else (lo, hi))
    try:
        _ARG_INFO_CACHE[executor] = result
    except TypeError:
        pass
    return result


@dataclasses.dataclass(frozen=True)
class Kernel:
    """An OpenCL kernel object: executor + structural work counts.

    ``executor(*arrays, **params) -> array | tuple[array]`` must be pure.
    ``counts(**params) -> WorkCounts`` derives the machine-model inputs from
    the problem size (shapes are passed through ``params`` by the caller).
    ``jitted=True`` marks executors that are already ``jax.jit``-wrapped
    (the ``repro.kernels.*.ops`` wrappers): the queue dispatches them
    directly instead of stacking a second jit on top.

    Host API v2 (``repro.core.program``): kernels created through a
    :class:`~repro.core.program.Program` additionally carry their registry
    identity — ``family`` (registry name), ``config`` (the
    :class:`~repro.core.device.EGPUConfig` they were built for) and
    ``variant`` (canonicalized builder keywords).  The serving layer keys
    graph caches on this identity instead of hashing executor closures.

    clSetKernelArg-style argument state: :attr:`arg_info` introspects the
    executor signature, :meth:`set_arg`/:meth:`set_args` stage arguments on
    the kernel object, and :meth:`CommandQueue.enqueue_kernel` launches with
    the staged arguments.  The staged state is *per kernel object* (and
    Program-created kernels are memoized singletons), so concurrent users
    staging different args on one kernel must pass args explicitly through
    ``enqueue_nd_range`` instead.
    """

    name: str
    executor: Callable[..., Any]
    counts: Optional[Callable[..., WorkCounts]] = None
    jitted: bool = False
    #: registry identity (set by Program.create_kernel; None for ad-hoc kernels)
    family: Optional[str] = None
    config: Optional[Any] = None            # EGPUConfig (hashable, frozen)
    variant: Tuple[Any, ...] = ()
    #: mutable clSetKernelArg storage; excluded from eq/hash so kernels stay
    #: usable as jit-cache keys
    args_state: _ArgState = dataclasses.field(
        default_factory=_ArgState, compare=False, repr=False)

    def with_identity(self, family: str, config: Any,
                      variant: Tuple[Any, ...]) -> "Kernel":
        """A copy of this kernel stamped with its registry identity."""
        return dataclasses.replace(self, family=family, config=config,
                                   variant=variant, args_state=_ArgState())

    # -- clGetKernelArgInfo --------------------------------------------------
    @property
    def arg_info(self) -> Optional[Tuple[ArgInfo, ...]]:
        """Executor argument metadata, or ``None`` when the executor's
        signature cannot be introspected (C builtins).  A ``*args``
        executor reports a single trailing variadic buffer entry named
        ``"*<name>"``.  Memoized per executor — APU stage wiring reads it
        on every offload."""
        return _introspect_executor(self.executor)[0]

    @property
    def n_buffer_args(self) -> Optional[Tuple[int, Optional[int]]]:
        """(min, max) buffer-argument arity; max is None for ``*args``
        executors, and the whole thing None when not introspectable.
        Defaulted positionals may be fed either a buffer or a param, so they
        widen max without raising min."""
        return _introspect_executor(self.executor)[1]

    # -- clSetKernelArg ------------------------------------------------------
    def set_args(self, *buffers: Any, **params: Any) -> "Kernel":
        """Stage positional buffer args and keyword params (clSetKernelArg
        for every index at once).  Non-:class:`Buffer` positionals are
        wrapped.  Returns ``self`` for chaining."""
        arity = self.n_buffer_args
        if arity is not None:
            lo, hi = arity
            if len(buffers) < lo or (hi is not None and len(buffers) > hi):
                bound = f"exactly {lo}" if hi == lo else (
                    f">= {lo}" if hi is None else f"{lo}..{hi}")
                raise ValueError(
                    f"kernel {self.name!r} takes {bound} buffer args, "
                    f"got {len(buffers)}")
        self.args_state.buffers = [
            b if isinstance(b, Buffer) else Buffer(b) for b in buffers]
        self.args_state.params = dict(params)
        return self

    def set_arg(self, index: int, value: Any) -> "Kernel":
        """clSetKernelArg: stage one argument by position.

        Buffer-kind indices take a :class:`Buffer` (or array, wrapped);
        param-kind indices stage the value under the parameter's name.
        """
        info = self.arg_info
        if info is None:
            raise TypeError(
                f"kernel {self.name!r} executor is not introspectable; "
                "use set_args(...) or pass args to enqueue_nd_range")
        if not 0 <= index < len(info):
            raise IndexError(
                f"kernel {self.name!r} has {len(info)} args, index {index} "
                "out of range")
        arg = info[index]
        if arg.kind == "param":
            self.args_state.params[arg.name] = value
            return self
        if arg.name.startswith("*"):
            raise ValueError(
                f"kernel {self.name!r} is variadic; stage buffers with "
                "set_args(...)")
        n_buf = sum(1 for a in info if a.kind == "buffer")
        if self.args_state.buffers is None:
            self.args_state.buffers = [None] * n_buf
        slot = sum(1 for a in info[:index] if a.kind == "buffer")
        self.args_state.buffers[slot] = (
            value if isinstance(value, Buffer) else Buffer(value))
        return self

    def staged_args(self) -> Tuple[Tuple["Buffer", ...], Dict[str, Any]]:
        """The staged (buffers, params) — raises if any buffer slot is unset."""
        st = self.args_state
        if st.buffers is None:
            raise RuntimeError(
                f"kernel {self.name!r} has no staged args; call set_args "
                "first (or pass args to enqueue_nd_range)")
        missing = [i for i, b in enumerate(st.buffers) if b is None]
        if missing:
            raise RuntimeError(
                f"kernel {self.name!r} buffer args {missing} are unset")
        return tuple(st.buffers), dict(st.params)


class Event:
    """Kernel-completion event: functional results + modeled time/energy.

    ``dispatch_s`` is the host-side time to *enqueue* the launch (the queue
    is asynchronous, so this excludes device compute); ``wait()`` blocks
    until the results are realized.  ``wall_s`` is kept as an alias of
    ``dispatch_s`` for older call sites.

    Events are reference-counted like ``cl_event`` (clRetainEvent /
    clReleaseEvent): :meth:`release` drops the event's hold on its output
    buffers once the count reaches zero, so a long-lived queue can return
    completed launches to O(in-flight) memory (see
    :meth:`CommandQueue.release_events`).  Modeled cost metadata survives
    release — only the (potentially large) functional outputs are dropped.
    """

    def __init__(self, kernel: Kernel, outputs: Tuple[Buffer, ...],
                 modeled: Optional[PhaseBreakdown], energy_j: Optional[float],
                 dispatch_s: float, deps: Tuple["Event", ...] = ()):
        self.kernel = kernel
        self.outputs = outputs
        self.modeled = modeled
        self.energy_j = energy_j
        self.dispatch_s = dispatch_s
        #: events this one waits on (explicit ``wait_events`` plus the
        #: in-order queue's implicit predecessor); cleared once realized or
        #: released so a long-lived queue never chains its whole history
        self.deps = tuple(deps)
        self._done = False
        self._refcount = 1

    @property
    def wall_s(self) -> float:
        """Alias of ``dispatch_s``.

        Deliberately readable on a *released* event (unlike :meth:`wait`,
        which raises): ``dispatch_s`` is O(1) cost metadata exactly like
        ``modeled`` / ``energy_j``, and the released-event contract keeps
        all three — release drops only the functional outputs.  Pinned by
        ``test_released_event_metadata_survives_profiling_window``.
        """
        return self.dispatch_s

    @property
    def done(self) -> bool:
        return self._done

    @property
    def released(self) -> bool:
        return self._refcount <= 0

    def retain(self) -> "Event":
        """clRetainEvent: keep output buffers alive across a queue release."""
        if self._refcount <= 0:
            raise RuntimeError("cannot retain a released Event")
        self._refcount += 1
        return self

    def release(self) -> None:
        """clReleaseEvent: drop one reference; at zero, free the outputs.

        Idempotent once released.  The modeled breakdown / energy stay
        readable (they are O(1)); only the buffer references are dropped.
        """
        if self._refcount <= 0:
            return
        self._refcount -= 1
        if self._refcount == 0:
            self.outputs = ()
            self.deps = ()

    def wait(self) -> Tuple[Buffer, ...]:
        """Block until this event (and its dependencies) completed.

        Waiting a *released* event is loud (``RuntimeError``), matching
        :meth:`retain` — the outputs are gone, so a silent empty return
        would hide a use-after-release bug.
        """
        if self.released:
            raise RuntimeError("cannot wait a released Event")
        # Iterative traversal: a long in-order chain of implicit deps must
        # not overflow the stack; already-realized or released deps prune.
        stack, seen, pending = [self], set(), []
        while stack:
            ev = stack.pop()
            if id(ev) in seen or ev._done or ev.released:
                continue
            seen.add(id(ev))
            pending.append(ev)
            stack.extend(ev.deps)
        for ev in pending:                 # realization order is immaterial:
            for b in ev.outputs:           # each blocks until its own work
                if isinstance(b.data, jax.Array):
                    b.data.block_until_ready()
            ev._done = True
            ev.deps = ()                   # realized: drop the chain refs
        return self.outputs


def _static_signature(params: Dict[str, Any]) -> Tuple[str, ...]:
    """Param names that must be jit-static (everything that isn't an array)."""
    return tuple(sorted(
        k for k, v in params.items()
        if not isinstance(v, (jax.Array, jnp.ndarray))))


#: sentinel kernel for marker/barrier events (clEnqueueMarkerWithWaitList /
#: clEnqueueBarrierWithWaitList) — never executed, carries no cost model
_MARKER = Kernel(name="marker", executor=lambda: ())

#: sentinel kernels identifying explicit data-movement commands; their
#: modeled cost is a transfer-only PhaseBreakdown attached per event/node
_WRITE = Kernel(name="write_buffer", executor=lambda x: (x,))
_READ = Kernel(name="read_buffer", executor=lambda x: (x,))
_COPY = Kernel(name="copy_buffer", executor=lambda x: (x,))
_TRANSFER_KINDS = {"write_buffer": "write", "read_buffer": "read",
                   "copy_buffer": "copy"}


class CommandQueue:
    """A command queue bound to one device.

    ``blocking=False`` (default) gives asynchronous OpenCL semantics: enqueue
    returns immediately and only ``Event.wait()`` / :meth:`finish`
    synchronize.  ``blocking=True`` restores eager-sync dispatch (one device
    round-trip per kernel) for overhead A/B comparisons.

    Ordering (``out_of_order``): an in-order queue (default) implicitly
    chains every launch after the previous one, exactly OpenCL's default
    queue semantics.  ``out_of_order=True`` is the
    ``CL_QUEUE_OUT_OF_ORDER_EXEC_MODE_ENABLE`` analogue: launches carry NO
    implicit ordering — dependencies come only from explicit
    ``wait_events=`` lists, dataflow (consuming a prior event's output
    buffers), and :meth:`enqueue_barrier` points.  Two launches with neither
    are *unordered* (concurrent in the machine model's critical path).
    Kernels are pure functions, so out-of-order execution can never change
    functional results — ordering is a synchronization and *modeling*
    contract, which :class:`CommandGraph` captures as a dependency DAG.

    Event lifecycle (serving workloads): an unprofiled queue auto-releases
    its events on :meth:`finish` — nobody can need them for accounting, so
    the queue stays O(in-flight) memory on a long-lived server.  A profiled
    queue keeps every event by default (full Fig-3/4 history); pass
    ``max_events=N`` for a *bounded profiling window*: only the newest N
    drained events are retained, older ones are released with their modeled
    time/energy folded into the queue's running totals, so
    :meth:`total_modeled_s` / :meth:`total_energy_j` stay exact regardless
    of the window.
    """

    def __init__(self, ctx: Context, profile: bool = True,
                 blocking: bool = False, max_events: Optional[int] = None,
                 out_of_order: bool = False, tracer: Optional[Any] = None,
                 trace_track: Optional[str] = None):
        if max_events is not None and max_events < 0:
            raise ValueError("max_events must be None or >= 0")
        self.ctx = ctx
        self.profile = profile
        self.blocking = blocking
        self.max_events = max_events
        self.out_of_order = out_of_order
        # Opt-in span tracing (ISSUE 7, repro.obs): every booked event
        # becomes one span on this queue's track, laid out end-to-end on
        # the queue's cumulative *modeled* timeline.  Strictly
        # observational — guarded at each booking site, so an untraced
        # queue (the default) allocates nothing from repro.obs.
        self._tracer = tracer
        self._trace_track = trace_track or f"queue:{ctx.device.config.name}"
        self._trace_t = 0.0
        self._barrier: Optional[Event] = None   # latest eager barrier event
        self._events: List[Event] = []
        self._drained = 0              # finish() watermark: events before
                                       # this index are already waited
        # Running totals of *released* events, so dropping an event from the
        # retained window never changes the queue's modeled accounting.
        self._released_count = 0
        self._released_modeled_s = 0.0
        self._released_energy_j = 0.0
        # Keyed on (kernel, static-arg signature): the same kernel enqueued
        # with a different static/traced split gets its own jit wrapper
        # instead of silently reusing the first call's (see ISSUE 1).
        self._jit_cache: Dict[Tuple[Kernel, Tuple[str, ...]], Callable] = {}
        self._capture: Optional[CommandGraph] = None

    # -- jit plumbing ------------------------------------------------------
    def _executor_for(self, kernel: Kernel, params: Dict[str, Any]) -> Callable:
        if kernel.jitted:
            return kernel.executor
        statics = _static_signature(params)
        key = (kernel, statics)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = jax.jit(kernel.executor, static_argnames=statics)
            self._jit_cache[key] = fn
        return fn

    def _model(self, kernel: Kernel, ndr: NDRange,
               counts_params: Dict[str, Any], resident: bool
               ) -> Tuple[Optional[PhaseBreakdown], Optional[float],
                          Optional[WorkCounts]]:
        """Machine-model (breakdown, energy, counts) of one enqueued command.

        The :class:`WorkCounts` actually priced (resident adjustment
        applied) ride along so a capture can pin them on its
        :class:`GraphNode` — downstream consumers (the serve engine's
        bytes-per-step roofline) read traffic straight off the captured
        schedule instead of re-deriving it.

        Operating-point audit (ISSUE 8): the config comes off the queue's
        device, so the breakdown is stamped with *that config's* clock
        (``PhaseBreakdown.freq_hz``) and energy prices at its (f, V) point —
        a graph captured at one DVFS point books honest numbers at any
        other, and downstream consumers (fusion, spikes, sharding, serve
        decomposition) all re-derive from the breakdown's own ``freq_hz``,
        never from a config default.
        """
        if not self.profile or kernel.counts is None:
            return None, None, None
        counts = kernel.counts(**counts_params)
        if resident:
            counts = dataclasses.replace(counts, host_bytes=0.0)
        cfg = self.ctx.device.config
        if self.ctx.device.is_host:
            modeled = host_time(counts, cfg)
            return modeled, host_energy_j(modeled), counts
        modeled = egpu_time(cfg, counts, ndr)
        return modeled, egpu_energy_j(cfg, modeled), counts

    def _trace_event(self, ev: "Event") -> None:
        """Record one booked event as a span on this queue's modeled
        timeline (only reached when a tracer is installed)."""
        dur = ev.modeled.total_s if ev.modeled is not None else 0.0
        self._tracer.span(ev.kernel.name, self._trace_t,
                          self._trace_t + dur, track=self._trace_track,
                          dispatch_s=ev.dispatch_s)
        self._trace_t += dur

    def _model_transfer(self, nbytes: float
                        ) -> Tuple[Optional[PhaseBreakdown], Optional[float]]:
        """Transfer-only cost of an explicit buffer command on this device."""
        if not self.profile:
            return None, None
        cfg = self.ctx.device.config
        modeled = transfer_time(cfg, nbytes)
        if self.ctx.device.is_host:
            return modeled, host_energy_j(modeled)
        return modeled, egpu_energy_j(cfg, modeled)

    def _check_wait_events(self, wait_events: Optional[Sequence[Event]]
                           ) -> Tuple[Event, ...]:
        evs = tuple(wait_events or ())
        for ev in evs:
            if not isinstance(ev, Event):
                raise TypeError(
                    f"wait_events must contain Events, got "
                    f"{type(ev).__name__}")
            if ev.released:
                raise RuntimeError(
                    "wait_events contains a released Event (use-after-"
                    "release)")
            if (self._capture is None
                    and getattr(ev, "_graph", None) is not None):
                raise RuntimeError(
                    "wait_events contains a capture-time Event; an eager "
                    "command can only wait events of executed launches")
        return evs

    def _implicit_deps(self) -> Tuple[Event, ...]:
        """The queue's implicit ordering edge for the next eager launch."""
        if not self.out_of_order:
            prev = self._events[-1] if self._events else None
            return (prev,) if prev is not None and not prev.released else ()
        if self._barrier is not None and not self._barrier.released:
            return (self._barrier,)
        return ()

    # -- the OpenCL-subset entry point -------------------------------------
    def enqueue_nd_range(self, kernel: Kernel, ndr: NDRange,
                         args: Sequence[Buffer],
                         params: Optional[Dict[str, Any]] = None,
                         counts_params: Optional[Dict[str, Any]] = None,
                         wait_events: Optional[Sequence[Event]] = None,
                         _resident: bool = False) -> Event:
        """Launch ``kernel`` over ``ndr`` with buffer ``args`` (non-blocking).

        ``params`` are executor kwargs (the paper's kernel-args region);
        ``counts_params`` are the problem sizes handed to the kernel's
        ``counts()`` for the machine model (defaults to ``params``).
        ``wait_events`` is the OpenCL ``event_wait_list``: events this
        launch must observe beyond its dataflow inputs.  On an in-order
        queue it adds edges on top of the implicit chain; on an
        ``out_of_order`` queue it is the ONLY explicit ordering (launches
        with no wait list and no dataflow link stay unordered).
        ``_resident=True`` marks a stage whose inputs are already resident
        in the unified memory / D$ (paper §IV-B pipeline chaining): the
        modeled host<->D$ transfer is waived for it.

        Inside a :meth:`capture` block the launch is recorded into the
        active :class:`CommandGraph` instead of executed; the returned
        event carries symbolic :class:`GraphBuffer` outputs and the
        dependency edges become graph nodes' ``deps``.
        """
        params = params or {}
        cp = counts_params if counts_params is not None else params
        waits = self._check_wait_events(wait_events)
        for i, b in enumerate(args):
            if not b.readable:
                raise ValueError(
                    f"kernel {kernel.name!r} arg {i} is a write-only "
                    f"(flags={b.flags!r}) buffer; kernels read their "
                    "arguments (CL_MEM_WRITE_ONLY violation)")
        if self._capture is not None:
            return self._capture._record(self, kernel, ndr, args, params, cp,
                                         _resident, waits)
        fn = self._executor_for(kernel, params)
        t0 = time.perf_counter()
        raw = fn(*[b.data for b in args], **params)
        if self.blocking:
            jax.block_until_ready(raw)
        dispatch = time.perf_counter() - t0
        outs = tuple(Buffer(r) for r in (raw if isinstance(raw, tuple) else (raw,)))

        modeled, energy, _counts = self._model(kernel, ndr, cp, _resident)
        deps = waits + self._implicit_deps()
        # Dataflow edges, mirroring capture's slot-producer tracking:
        # consuming another launch's output buffer is an ordering edge even
        # on an out-of-order queue (and across queues), so wait() realizes
        # the producer's done-flag transitively.
        for b in args:
            producer = getattr(b, "_event", None)
            if (producer is not None and not producer._done
                    and not producer.released and producer not in deps):
                deps += (producer,)
        ev = Event(kernel, outs, modeled, energy, dispatch, deps=deps)
        for b in outs:
            b._event = ev
        if self.blocking:
            ev._done = True
            ev.deps = ()
        self._events.append(ev)
        if self._tracer is not None:
            self._trace_event(ev)
        return ev

    def enqueue_kernel(self, kernel: Kernel, ndr: Optional[NDRange] = None,
                       counts_params: Optional[Dict[str, Any]] = None,
                       wait_events: Optional[Sequence[Event]] = None,
                       _resident: bool = False) -> Event:
        """clEnqueueNDRangeKernel over the kernel's *staged* arguments.

        The OpenCL-shaped companion to :meth:`enqueue_nd_range`: arguments
        come from :meth:`Kernel.set_args` / :meth:`Kernel.set_arg` instead
        of the call site.  ``ndr`` defaults to the paper's §VIII-B optimal
        NDRange for the first buffer's element count on this queue's device.
        """
        bufs, params = kernel.staged_args()
        if ndr is None:
            if not bufs:
                raise ValueError(
                    "enqueue_kernel needs an explicit NDRange for a kernel "
                    "with no buffer args")
            ndr = optimal_ndrange(int(bufs[0].data.size),
                                  self.ctx.device.config)
        return self.enqueue_nd_range(kernel, ndr, bufs, params=params,
                                     counts_params=counts_params,
                                     wait_events=wait_events,
                                     _resident=_resident)

    # -- explicit data movement (host API v2) -------------------------------
    def _transfer_event(self, kernel: Kernel, outputs: Tuple[Buffer, ...],
                        nbytes: float, waits: Tuple[Event, ...],
                        producers: Sequence[Buffer], blocking: bool) -> Event:
        """Eager transfer command: modeled cost + event-DAG bookkeeping."""
        deps = waits + self._implicit_deps()
        for b in producers:
            producer = getattr(b, "_event", None)
            if (producer is not None and not producer._done
                    and not producer.released and producer not in deps):
                deps += (producer,)
        modeled, energy = self._model_transfer(nbytes)
        ev = Event(kernel, outputs, modeled, energy, 0.0, deps=deps)
        if self.blocking or blocking:
            ev.wait()
        self._events.append(ev)
        if self._tracer is not None:
            self._trace_event(ev)
        return ev

    @staticmethod
    def _check_aval_match(what: str, data: Any, buf: Buffer) -> None:
        if tuple(data.shape) != tuple(buf.shape) or data.dtype != buf.dtype:
            raise ValueError(
                f"{what}: source {tuple(data.shape)}/{data.dtype} does not "
                f"match destination buffer {tuple(buf.shape)}/{buf.dtype} "
                "(sub-buffer offsets are not supported)")

    def enqueue_write_buffer(self, buf: Buffer, src: Any,
                             wait_events: Optional[Sequence[Event]] = None,
                             blocking: bool = False) -> Event:
        """clEnqueueWriteBuffer: move host data into ``buf`` (host -> D$).

        A first-class command: it returns a real :class:`Event`, is costed
        as a transfer-only :class:`PhaseBreakdown` from the device's bus
        parameters, obeys the queue's ordering rules (implicit chain /
        ``wait_events`` / barriers) and — under :meth:`capture` — records a
        transfer :class:`GraphNode`, so the DAG critical path can overlap
        it with compute on independent branches.  ``buf`` must be writable;
        later commands consuming ``buf`` observe the written value (the
        event is ``buf``'s new producer).  ``blocking=True`` is CL_TRUE:
        wait before returning.
        """
        waits = self._check_wait_events(wait_events)
        if not buf.writable:
            raise ValueError(
                f"enqueue_write_buffer into a read-only buffer "
                f"(flags={buf.flags!r}) — CL_MEM_READ_ONLY violation")
        if isinstance(src, Buffer) and not src.readable:
            raise ValueError(
                f"enqueue_write_buffer from a write-only source "
                f"(flags={src.flags!r}) — CL_MEM_WRITE_ONLY violation")
        if self._capture is not None:
            return self._capture._record_transfer(self, "write", buf, src,
                                                  waits)
        if isinstance(buf, GraphBuffer):
            raise RuntimeError(
                "cannot write a GraphBuffer eagerly; it has no storage "
                "outside its graph's launch")
        data = src.data if isinstance(src, Buffer) else jnp.asarray(src)
        if not isinstance(data, jax.Array):
            raise RuntimeError(
                "enqueue_write_buffer source must hold concrete data "
                "(GraphBuffer sources are capture-only)")
        self._check_aval_match("enqueue_write_buffer", data, buf)
        producers = (src, buf) if isinstance(src, Buffer) else (buf,)
        buf.data = data
        ev = self._transfer_event(_WRITE, (buf,), buf.nbytes, waits,
                                  producers, blocking)
        buf._event = ev
        return ev

    def enqueue_read_buffer(self, buf: Buffer,
                            wait_events: Optional[Sequence[Event]] = None,
                            blocking: bool = False) -> Event:
        """clEnqueueReadBuffer: move ``buf`` to the host (D$ -> host).

        Under unified memory the returned event's output *is* the buffer
        (no copy is made), but the command is costed as a real transfer over
        the host bus and participates in event ordering and graph capture —
        a capture ending in read commands returns the read-back values as
        the graph's outputs.  ``buf`` must be readable.
        """
        waits = self._check_wait_events(wait_events)
        if not buf.readable:
            raise ValueError(
                f"enqueue_read_buffer from a write-only buffer "
                f"(flags={buf.flags!r}) — CL_MEM_WRITE_ONLY violation")
        if self._capture is not None:
            return self._capture._record_transfer(self, "read", buf, None,
                                                  waits)
        if isinstance(buf, GraphBuffer):
            raise RuntimeError(
                "cannot read a GraphBuffer eagerly; launch its graph and "
                "read the outputs instead")
        return self._transfer_event(_READ, (buf,), buf.nbytes, waits, (buf,),
                                    blocking)

    def enqueue_copy_buffer(self, src: Buffer, dst: Buffer,
                            wait_events: Optional[Sequence[Event]] = None
                            ) -> Event:
        """clEnqueueCopyBuffer: device-side copy ``src`` -> ``dst``.

        ``src`` must be readable and ``dst`` writable, with matching
        shape/dtype.  Costed as one bus transfer of ``src.nbytes``; after
        the event, ``dst`` holds ``src``'s value (kernels are functional and
        arrays immutable, so the unified-memory copy is an alias).
        """
        waits = self._check_wait_events(wait_events)
        if not src.readable:
            raise ValueError(
                f"enqueue_copy_buffer from a write-only source "
                f"(flags={src.flags!r})")
        if not dst.writable:
            raise ValueError(
                f"enqueue_copy_buffer into a read-only destination "
                f"(flags={dst.flags!r}) — CL_MEM_READ_ONLY violation")
        self._check_aval_match("enqueue_copy_buffer", src.data, dst)
        if self._capture is not None:
            return self._capture._record_transfer(self, "copy", dst, src,
                                                  waits)
        if isinstance(src, GraphBuffer) or isinstance(dst, GraphBuffer):
            raise RuntimeError(
                "cannot copy GraphBuffers eagerly; they have no storage "
                "outside their graph's launch")
        dst.data = src.data
        ev = self._transfer_event(_COPY, (dst,), src.nbytes, waits,
                                  (src, dst), blocking=False)
        dst._event = ev
        return ev

    # -- synchronization commands ------------------------------------------
    def enqueue_marker(self, wait_events: Optional[Sequence[Event]] = None
                       ) -> Event:
        """clEnqueueMarkerWithWaitList: an event completing once
        ``wait_events`` (default: everything enqueued on this queue so far)
        have completed.  Carries no cost model and no outputs."""
        return self._enqueue_sync(wait_events, barrier=False)

    def enqueue_barrier(self, wait_events: Optional[Sequence[Event]] = None
                        ) -> Event:
        """clEnqueueBarrierWithWaitList: like :meth:`enqueue_marker`, but
        also an ordering point — every *subsequent* launch on an
        ``out_of_order`` queue implicitly depends on it (in-order queues
        already chain, so there it only returns the aggregate event)."""
        return self._enqueue_sync(wait_events, barrier=True)

    def _enqueue_sync(self, wait_events: Optional[Sequence[Event]],
                      barrier: bool) -> Event:
        # OpenCL: an empty wait list means "all previously enqueued
        # commands", same as passing none at all.
        waits = self._check_wait_events(wait_events) or None
        if self._capture is not None:
            return self._capture._record_sync(self, waits, barrier)
        if waits:
            # The queue's ordering rules still apply to the marker itself
            # (in-order: chained after the previous command; out-of-order:
            # after the latest barrier).
            deps = waits + self._implicit_deps()
        else:
            # Events before the finish()/drain() watermark are already
            # realized and contribute nothing — snapshotting them would
            # make each marker O(history) on a long-lived profiled queue.
            deps = tuple(e for e in self._events[self._drained:]
                         if not e.released)
        ev = Event(_MARKER, (), None, None, 0.0, deps=deps)
        self._events.append(ev)
        if self._tracer is not None:
            self._trace_event(ev)
        if barrier:
            self._barrier = ev
        return ev

    # -- graph capture ------------------------------------------------------
    def capture(self) -> "CommandGraph":
        """Record subsequent enqueues into a :class:`CommandGraph`.

        Use as a context manager::

            with q.capture() as graph:
                q.enqueue_nd_range(k1, ndr, (a, b))   # recorded, not run
                ...
            outs = graph.launch()                      # one fused dispatch

        Launches inside the block are traced abstractly (``jax.eval_shape``)
        so capture itself never touches the device.
        """
        return CommandGraph(self)

    def flush(self) -> None:
        """clFlush — dispatch is eager under JAX, so this is a no-op."""

    def finish(self) -> None:
        """Block until every enqueued kernel completed (clFinish).

        Only events enqueued since the last ``finish()`` are waited (a
        drained-watermark: repeated drains on a long-lived queue stay O(new
        work), not O(full history)).  On an unprofiled queue the drained
        events are then released outright; with ``max_events`` set, the
        retained history is trimmed to the window (oldest first)."""
        for ev in self._events[self._drained:]:
            if not ev.released:            # user-released mid-history: the
                ev.wait()                  # outputs are gone, nothing to wait
        self._drained = len(self._events)
        if not self.profile:
            self.release_events()
        elif (self.max_events is not None
              and len(self._events) > self.max_events):
            self.release_events(upto=len(self._events) - self.max_events)

    def drain(self, n: int) -> None:
        """Wait the oldest ``n`` retained events (a *partial* clFinish).

        Starts at the ``finish()`` watermark — events a previous drain
        already realized are never re-waited, so repeated partial drains on
        a long-lived queue stay O(new work), not O(history).  Lets a
        serving layer retire one launch's event segment without
        synchronizing launches enqueued after it — pair with
        ``release_events(upto=n)`` to drop exactly that segment."""
        n = min(n, len(self._events))
        for ev in self._events[self._drained:n]:
            if not ev.released:
                ev.wait()
        self._drained = max(self._drained, n)

    def release_events(self, upto: Optional[int] = None) -> int:
        """Release and drop the oldest ``upto`` events (clReleaseEvent sweep).

        Only *drained* events are eligible — an event :meth:`finish` has not
        waited yet may still be in flight.  Each dropped event's modeled
        time/energy is folded into the queue's running totals first, so
        :meth:`total_modeled_s` / :meth:`total_energy_j` are unaffected.
        ``Event.retain()``-ed events are still dropped from the queue's
        history, but keep their output buffers alive for the holder.
        Returns the number of events released.
        """
        upto = self._drained if upto is None else min(upto, self._drained)
        if upto <= 0:
            return 0
        for ev in self._events[:upto]:
            if ev.modeled is not None:
                self._released_modeled_s += ev.modeled.total_s
            if ev.energy_j is not None:
                self._released_energy_j += ev.energy_j
            self._released_count += 1
            ev.release()
        del self._events[:upto]
        self._drained -= upto
        return upto

    @property
    def events(self) -> Tuple[Event, ...]:
        """Retained (not yet released) events, oldest first."""
        return tuple(self._events)

    @property
    def released_count(self) -> int:
        """Events released from this queue's history so far."""
        return self._released_count

    def total_modeled_s(self) -> float:
        # `is not None`, not truthiness: an all-zero PhaseBreakdown (e.g. a
        # fully resident stage) must still be counted.  Released events are
        # accounted via the running totals.
        return self._released_modeled_s + sum(
            e.modeled.total_s for e in self._events if e.modeled is not None)

    def total_energy_j(self) -> float:
        return self._released_energy_j + sum(
            e.energy_j for e in self._events if e.energy_j is not None)


@dataclasses.dataclass
class GraphNode:
    """One captured launch: kernel + wiring + capture-time machine model."""

    kernel: Kernel
    call: Callable[..., Any]            # executor with params pre-bound
    in_slots: Tuple[int, ...]
    out_slots: Tuple[int, ...]
    out_avals: Tuple[jax.ShapeDtypeStruct, ...]
    modeled: Optional[PhaseBreakdown]
    energy_j: Optional[float]
    n_items: int = 0                    # first input's element count (the
                                        # NDRange sizing the eager path uses)
    #: indices of earlier nodes this one depends on (dataflow slots +
    #: wait_events + the enqueueing queue's ordering rules) — the edges of
    #: the event-dependency DAG the critical-path model walks
    deps: Tuple[int, ...] = ()
    #: node class: "kernel", "sync" (marker/barrier), or an explicit
    #: transfer command — "write" / "read" / "copy"
    kind: str = "kernel"
    #: bytes moved over the host bus (transfer nodes only)
    nbytes: float = 0.0
    #: the WorkCounts this node was priced with at capture (resident
    #: adjustment applied; ``None`` for sync/transfer nodes and unprofiled
    #: queues) — lets consumers read modeled traffic straight off the
    #: captured schedule (the serve engine's bytes/step roofline)
    counts: Optional[WorkCounts] = None
    #: slots whose logical buffer this (write/copy) node's output REBINDS —
    #: the destination's previous value.  Slots are SSA, so without this
    #: the overwrite relationship is gone after capture; the graph
    #: sanitizer (repro.analyze) re-proves the WAR/WAW ordering edges the
    #: capture added for it
    overwrites: Tuple[int, ...] = ()

    @property
    def is_transfer(self) -> bool:
        return self.kind in ("write", "read", "copy")


class CommandGraph:
    """A captured kernel DAG, launched as one fused XLA computation.

    Built by :meth:`CommandQueue.capture`.  While capturing, every
    ``enqueue_nd_range`` appends a :class:`GraphNode`: inputs are resolved to
    *slots* — either graph-external buffers (concrete data seen during
    capture) or earlier nodes' outputs — and output shapes come from
    ``jax.eval_shape``, so nothing executes.  Each node also records its
    dependency edges: dataflow (consuming an earlier node's output slot),
    explicit ``wait_events``, and the enqueueing queue's ordering rules
    (implicit chaining on in-order queues, barrier frontiers on out-of-order
    ones).  Markers and barriers are recorded as zero-cost, output-less
    nodes, so OpenCL's transitive ordering falls out of the DAG structure.
    :meth:`join` records enqueues from *additional* queues (e.g. the
    host's) into the same capture, so one graph can hold host + e-GPU nodes
    with cross-queue event edges.

    :meth:`launch` replays all nodes inside a single ``jax.jit``; the
    graph's outputs are the final kernel node's outputs.  Launches bind to the
    *caller's* queue (``launch(..., queue=...)``): events and modeled
    totals land on the queue that launched, not the one that captured —
    a cached graph shared by several serving workers keeps every worker's
    accounting separate.

    Per-node ``modeled`` / ``energy_j`` come from the captured schedule
    (``WorkCounts`` at capture time) on the enqueueing queue's device,
    giving the same per-stage Fig-3/Fig-4 accounting as eager dispatch
    while the wall-clock path is fused; :meth:`fused_modeled` walks the
    dependency DAG's critical path, so concurrent branches overlap instead
    of summing.

    A sealed graph is named after its kernel nodes (``engine.generate``;
    a pipeline's stages joined by ``+``): the name labels its profiler
    spans, and its compiled program is ``jit_<name>`` on the device.
    """

    def __init__(self, queue: CommandQueue):
        self.queue = queue                     # home queue: default binding
        self.name = "graph"                    # its kernels', once sealed
        self.queues: List[CommandQueue] = [queue]
        self.nodes: List[GraphNode] = []
        self._n_slots = 0
        self._ext_slots: List[int] = []        # slot index of each external
        self._ext_values: List[jax.Array] = [] # captured concrete externals
        self._ext_avals: List[jax.ShapeDtypeStruct] = []
        self._buf_slot: Dict[int, int] = {}    # id(Buffer) -> slot
        self._bufs_alive: List[Buffer] = []    # keep ids stable during capture
        self._slot_producer: Dict[int, int] = {}   # slot -> producing node
        self._slot_readers: Dict[int, List[int]] = {}  # slot -> consumer nodes
        self._queue_nodes: Dict[int, List[int]] = {}   # id(queue) -> nodes
        self._last_node: Dict[int, int] = {}   # id(queue) -> last node idx
        self._barrier_node: Dict[int, int] = {}  # out-of-order barrier point
        self._jit_cache: Dict[Tuple[Any, ...], Callable] = {}
        self._sealed = False
        self._fused_memo: Optional[Tuple[Optional[PhaseBreakdown], float]] = None
        #: slot -> CL_MEM-style access flags of the buffer behind it, so
        #: the sanitizer can re-check flag discipline after capture
        self._slot_flags: Dict[int, str] = {}
        #: verify() results per donation tuple — verification is a pure
        #: function of the sealed capture, so warm serving pays one dict
        #: lookup at most (and zero when REPRO_VERIFY is off)
        self._verify_memo: Dict[Tuple[int, ...], Tuple[Any, ...]] = {}

    # -- capture ------------------------------------------------------------
    def __enter__(self) -> "CommandGraph":
        if self.queue._capture is not None:
            raise RuntimeError("CommandQueue is already capturing")
        self.queue._capture = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        for q in self.queues:                  # joined queues too
            if q._capture is self:
                q._capture = None
        # Only a capture body that completed cleanly yields a launchable
        # graph; an exception mid-capture leaves a truncated chain.
        self._sealed = exc_type is None
        self.name = "+".join(n.kernel.name for n in self.nodes
                             if n.kind == "kernel") or "graph"
        # REPRO_VERIFY=1 (repro.analyze): sanitize every capture at seal
        # time, so a whole test/bench run doubles as a sanitizer sweep.
        if (self._sealed and self.nodes
                and os.environ.get("REPRO_VERIFY") == "1"):
            findings = self.verify()
            if findings:
                from ..analyze.graph import GraphVerifyError
                raise GraphVerifyError(findings)

    def join(self, queue: CommandQueue) -> "_GraphJoin":
        """Record enqueues on another queue into this capture.

        Use as a context manager *inside* the capture block to build a
        multi-queue graph (host + e-GPU nodes in one capture)::

            with egpu_q.capture() as graph:
                pre = egpu_q.enqueue_nd_range(k_pre, ndr, (a,))
                with graph.join(host_q):
                    post = host_q.enqueue_nd_range(k_post, ndr_h, pre.outputs,
                                                   wait_events=[pre])

        Each node is costed with its *own* queue's device model (the host
        node above uses the scalar-host machine model), and cross-queue
        ``wait_events`` become ordinary DAG edges.
        """
        return _GraphJoin(self, queue)

    def _slot_of(self, buf: Buffer) -> int:
        slot = self._buf_slot.get(id(buf))
        if slot is None:
            if isinstance(buf, GraphBuffer):
                raise RuntimeError(
                    "GraphBuffer from a different capture passed as input")
            slot = self._new_slot()
            self._buf_slot[id(buf)] = slot
            self._bufs_alive.append(buf)
            self._slot_flags[slot] = buf.flags
            self._ext_slots.append(slot)
            self._ext_values.append(buf.data)
            self._ext_avals.append(
                jax.ShapeDtypeStruct(buf.data.shape, buf.data.dtype))
        return slot

    def _new_slot(self) -> int:
        s = self._n_slots
        self._n_slots += 1
        return s

    def _dep_nodes_of(self, ev: Event) -> frozenset:
        """Node indices an event stands for (capture-time events only)."""
        nodes = getattr(ev, "_dep_nodes", None)
        if nodes is None or getattr(ev, "_graph", None) is not self:
            raise RuntimeError(
                "wait_events during capture must be events returned by this "
                "capture (eager or foreign-graph events have no node "
                "identity here)")
        return nodes

    def _record(self, queue: CommandQueue, kernel: Kernel, ndr: NDRange,
                args: Sequence[Buffer], params: Dict[str, Any],
                counts_params: Dict[str, Any], resident: bool,
                wait_events: Tuple[Event, ...] = ()) -> Event:
        in_slots = tuple(self._slot_of(b) for b in args)
        in_avals = tuple(
            jax.ShapeDtypeStruct(b.data.shape, b.data.dtype) for b in args)

        def call(*arrays, _exe=kernel.executor, _params=dict(params)):
            out = _exe(*arrays, **_params)
            return out if isinstance(out, tuple) else (out,)

        out_avals = tuple(jax.eval_shape(call, *in_avals))
        out_slots = tuple(self._new_slot() for _ in out_avals)
        # Cost the node on the ENQUEUEING queue's device: a multi-queue
        # capture mixes host and e-GPU nodes, each with its own model.
        modeled, energy, counts = queue._model(kernel, ndr, counts_params,
                                               resident)

        # Dependency edges: dataflow + wait_events + queue ordering.
        deps = set()
        for s in in_slots:
            producer = self._slot_producer.get(s)
            if producer is not None:
                deps.add(producer)
        for ev in wait_events:
            deps.update(self._dep_nodes_of(ev))
        deps.update(self._queue_order_deps(queue))
        idx = self._append_node(
            queue, GraphNode(kernel, call, in_slots, out_slots,
                             out_avals, modeled, energy,
                             n_items=int(args[0].data.size) if args else 0,
                             deps=tuple(sorted(deps)), counts=counts))
        for s in in_slots:
            self._slot_readers.setdefault(s, []).append(idx)
        for s in out_slots:
            self._slot_producer[s] = idx
            self._slot_flags[s] = "rw"      # kernel outputs: fresh rw slots
        outs = tuple(GraphBuffer(a, s) for a, s in zip(out_avals, out_slots))
        for b in outs:
            self._buf_slot[id(b)] = b.slot
            self._bufs_alive.append(b)
        ev = Event(kernel, outs, modeled, energy, 0.0)
        ev._graph = self
        ev._dep_nodes = frozenset((idx,))
        return ev

    def _queue_order_deps(self, queue: CommandQueue) -> Tuple[int, ...]:
        """The enqueueing queue's implicit ordering edge for the next node:
        its previous command (in-order) or its latest barrier node
        (out-of-order).  One edge — earlier constraints flow transitively
        through the chain / barrier nodes."""
        qid = id(queue)
        if not queue.out_of_order:
            last = self._last_node.get(qid)
            return () if last is None else (last,)
        bar = self._barrier_node.get(qid)
        return () if bar is None else (bar,)

    def _append_node(self, queue: CommandQueue, node: GraphNode) -> int:
        qid = id(queue)
        idx = len(self.nodes)
        self.nodes.append(node)
        self._queue_nodes.setdefault(qid, []).append(idx)
        self._last_node[qid] = idx
        return idx

    def _record_sync(self, queue: CommandQueue,
                     wait_events: Optional[Tuple[Event, ...]],
                     barrier: bool) -> Event:
        """Capture-time marker/barrier: a zero-cost :class:`GraphNode`.

        Recording sync commands as real (modeled-``None``, output-less)
        nodes makes OpenCL's transitivity structural: a later marker's
        default wait list ("all previously enqueued commands") includes
        earlier sync nodes and hence — through THEIR edges — cross-queue
        dependencies; a new barrier chains to the previous barrier node, so
        every earlier barrier's constraint keeps reaching later launches
        with O(1) edges per node.  The critical-path model treats them as
        zero-cost pass-throughs."""
        qid = id(queue)
        deps = set(self._queue_order_deps(queue))
        if not wait_events:
            # None or empty: all commands enqueued on this queue so far
            # (sync nodes included — that's what carries transitivity).
            deps.update(self._queue_nodes.get(qid, ()))
        else:
            # _queue_order_deps already chained this command after the
            # queue's latest barrier (out-of-order) or predecessor
            # (in-order), so an earlier barrier's constraint persists
            # alongside the explicit list.
            for e in wait_events:
                deps.update(self._dep_nodes_of(e))
        idx = self._append_node(
            queue, GraphNode(_MARKER, lambda: (), (), (), (),
                             None, None, n_items=0,
                             deps=tuple(sorted(deps)), kind="sync"))
        if barrier:
            self._barrier_node[qid] = idx
        ev = Event(_MARKER, (), None, None, 0.0)
        ev._graph = self
        ev._dep_nodes = frozenset((idx,))
        return ev

    def _record_transfer(self, queue: CommandQueue, kind: str, buf: Buffer,
                         src: Any, wait_events: Tuple[Event, ...]) -> Event:
        """Capture an explicit transfer command as a real :class:`GraphNode`.

        The node's ``call`` is identity (XLA elides it inside the fused
        computation — under unified memory the data never actually moves),
        but it carries the transfer-only machine model and full dependency
        edges, so ``fused_modeled()``'s critical path prices the traffic
        and can overlap it with compute on independent branches.

        Slot wiring per command:

        * ``write``: the host source becomes an input slot (an *external*
          when it is fresh data — ``launch_prefix`` can then feed new
          request payloads straight through write nodes); the destination
          buffer is **rebound** to the node's output slot, so later
          consumers of ``buf`` depend on the write.  The old binding (if
          any) contributes a write-after-read/write ordering edge.
        * ``read``: consumes the buffer's current slot, produces a fresh
          slot holding the host copy; the buffer keeps its binding.
        * ``copy``: consumes the source's slot, rebinds the destination.
        """
        if kind == "write":
            src_buf = src if isinstance(src, Buffer) else Buffer(src)
            CommandQueue._check_aval_match("enqueue_write_buffer",
                                           src_buf.data, buf)
            in_buf, rebind = src_buf, buf
            sentinel, out_flags = _WRITE, buf.flags
        elif kind == "read":
            in_buf, rebind = buf, None
            sentinel, out_flags = _READ, buf.flags
        else:
            CommandQueue._check_aval_match("enqueue_copy_buffer",
                                           src.data, buf)
            in_buf, rebind = src, buf
            sentinel, out_flags = _COPY, buf.flags
        in_slot = self._slot_of(in_buf)
        aval = jax.ShapeDtypeStruct(tuple(in_buf.data.shape),
                                    in_buf.data.dtype)
        nbytes = float(aval.size * aval.dtype.itemsize)
        modeled, energy = queue._model_transfer(nbytes)

        deps = set()
        overwrites: Tuple[int, ...] = ()
        producer = self._slot_producer.get(in_slot)
        if producer is not None:
            deps.add(producer)
        if rebind is not None:
            # write-after-write on the destination's old producer, plus
            # write-after-read on every node that consumed the old value —
            # an overwrite must not model as concurrent with readers of the
            # value it replaces
            prev_slot = self._buf_slot.get(id(rebind))
            if prev_slot is not None:
                prev_producer = self._slot_producer.get(prev_slot)
                if prev_producer is not None:
                    deps.add(prev_producer)
                deps.update(self._slot_readers.get(prev_slot, ()))
                overwrites = (prev_slot,)    # sanitizer re-proves the edges
        for ev in wait_events:
            deps.update(self._dep_nodes_of(ev))
        deps.update(self._queue_order_deps(queue))

        out_slot = self._new_slot()
        idx = self._append_node(
            queue, GraphNode(sentinel, lambda x: (x,), (in_slot,),
                             (out_slot,), (aval,), modeled, energy,
                             n_items=int(aval.size),
                             deps=tuple(sorted(deps)), kind=kind,
                             nbytes=nbytes, overwrites=overwrites))
        self._slot_readers.setdefault(in_slot, []).append(idx)
        self._slot_producer[out_slot] = idx
        self._slot_flags[out_slot] = out_flags
        if rebind is not None:
            self._buf_slot[id(rebind)] = out_slot
            self._bufs_alive.append(rebind)
        out = GraphBuffer(aval, out_slot, flags=out_flags)
        self._buf_slot[id(out)] = out_slot
        self._bufs_alive.append(out)
        ev = Event(sentinel, (out,), modeled, energy, 0.0)
        ev._graph = self
        ev._dep_nodes = frozenset((idx,))
        return ev

    # -- accounting ---------------------------------------------------------
    @property
    def n_external(self) -> int:
        return len(self._ext_slots)

    @property
    def ext_avals(self) -> Tuple[jax.ShapeDtypeStruct, ...]:
        """Shape/dtype of each external input, in capture order."""
        return tuple(self._ext_avals)

    def modeled_breakdowns(self) -> Tuple[Optional[PhaseBreakdown], ...]:
        return tuple(n.modeled for n in self.nodes)

    def node_deps(self) -> Tuple[Tuple[int, ...], ...]:
        """Per-node dependency edges (indices into :attr:`nodes`)."""
        return tuple(n.deps for n in self.nodes)

    def verify(self, donate: Sequence[int] = ()) -> Tuple[Any, ...]:
        """Statically sanitize the captured DAG (see :mod:`repro.analyze`).

        Returns the :class:`~repro.analyze.graph.Finding` tuple — empty for
        a hazard-free capture.  ``donate`` lists donated external-input
        positions (capture order), enabling the use-after-donate /
        double-donation checks.  Results are memoized per donation tuple:
        verification is a pure function of the sealed capture, so a warm
        serving path re-verifying before every donating launch pays one
        dict lookup, never a re-walk.
        """
        key = tuple(sorted(int(i) for i in donate))
        memo = self._verify_memo.get(key)
        if memo is None:
            from ..analyze.graph import verify_graph
            memo = verify_graph(self, donate=key)
            self._verify_memo[key] = memo
        return memo

    def total_modeled_s(self) -> float:
        return sum(n.modeled.total_s for n in self.nodes
                   if n.modeled is not None)

    def total_energy_j(self) -> float:
        return sum(n.energy_j for n in self.nodes if n.energy_j is not None)

    def fused_modeled(self) -> Tuple[Optional[PhaseBreakdown], float]:
        """(fused breakdown, total energy) of the captured DAG, memoized.

        The breakdown is the *critical path* through the dependency DAG
        (:func:`~repro.core.machine.fuse_breakdowns` with ``deps``):
        concurrent branches of an out-of-order capture overlap instead of
        summing, while a linear in-order chain reproduces the classic
        chain fusion exactly.  Energy is total work — it sums over every
        node regardless of concurrency.  Both come from capture time and
        never change across launches — the serving hot path reads them
        once per launch, so re-walking the node list every time would be
        pure waste.  The breakdown is ``None`` when no node carries a
        machine model.
        """
        if self._fused_memo is None:
            mods = self.modeled_breakdowns()
            fused = (fuse_breakdowns(mods, deps=self.node_deps())
                     if any(m is not None for m in mods) else None)
            self._fused_memo = (fused, self.total_energy_j())
        return self._fused_memo

    @property
    def out_avals(self) -> Tuple[jax.ShapeDtypeStruct, ...]:
        """Shape/dtype of each launch output, in output order (what a
        serving layer needs to derive per-output shardings before any
        launch happened)."""
        slot_aval: Dict[int, jax.ShapeDtypeStruct] = {}
        for node in self.nodes:
            for s, a in zip(node.out_slots, node.out_avals):
                slot_aval[s] = a
        return tuple(slot_aval[s] for s in self._output_slots())

    # -- launch -------------------------------------------------------------
    def _output_slots(self) -> Tuple[int, ...]:
        """The slots a launch returns.

        Trailing ``read_buffer`` nodes define the outputs (a capture ending
        in explicit reads returns the read-back values, one per read, in
        enqueue order — markers/barriers in between are ignored); otherwise
        the last node with outputs, so a trailing marker/barrier never eats
        them.
        """
        reads: List[GraphNode] = []
        for node in reversed(self.nodes):
            if node.kind == "read":
                reads.append(node)
            elif node.out_slots:
                break
        if reads:
            return tuple(s for n in reversed(reads) for s in n.out_slots)
        return next(n.out_slots for n in reversed(self.nodes) if n.out_slots)

    def _fused(self, donate: Tuple[int, ...],
               in_shardings: Optional[Tuple[Any, ...]] = None,
               out_shardings: Optional[Tuple[Any, ...]] = None,
               per_device: bool = False) -> Callable:
        # One compiled executable per (donation, mesh binding): the same
        # captured graph serves single-device and sharded launches side by
        # side — shardings are a launch-time property, never part of the
        # capture (NamedShardings hash by mesh + spec, so the key is cheap).
        key = (donate, in_shardings, out_shardings, per_device)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn

        nodes = tuple(self.nodes)
        ext_slots = tuple(self._ext_slots)
        out_slots = self._output_slots()
        n_slots = self._n_slots

        def run(*ext):
            vals: List[Any] = [None] * n_slots
            for slot, v in zip(ext_slots, ext):
                vals[slot] = v
            for node in nodes:
                outs = node.call(*[vals[s] for s in node.in_slots])
                for slot, o in zip(node.out_slots, outs):
                    vals[slot] = o
            return tuple(vals[s] for s in out_slots)

        # the compiled program's name on the device (``jit_<name>``)
        run.__name__ = run.__qualname__ = self.name
        jit_kwargs: Dict[str, Any] = {}
        if in_shardings is not None:
            jit_kwargs["in_shardings"] = in_shardings
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        body = run
        if per_device:
            body = jax.shard_map(
                run, mesh=in_shardings[0].mesh,
                in_specs=tuple(sh.spec for sh in in_shardings),
                out_specs=tuple(sh.spec for sh in out_shardings),
                check_vma=False)
        fn = jax.jit(body, donate_argnums=donate, **jit_kwargs)
        self._jit_cache[key] = fn
        return fn

    def launch(self, *inputs: Any, donate: Sequence[int] = (),
               queue_events: bool = True,
               queue: Optional[CommandQueue] = None,
               in_shardings: Optional[Sequence[Any]] = None,
               out_shardings: Optional[Sequence[Any]] = None,
               per_device: bool = False
               ) -> Tuple[Buffer, ...]:
        """Execute the captured chain as one fused dispatch (non-blocking).

        ``inputs`` replace the graph's external buffers in capture order
        (shapes/dtypes must match); with no inputs the arrays captured at
        record time are reused.  ``donate`` lists external-input positions
        whose device buffers XLA may reuse for the computation (jit
        ``donate_argnums``); never pass an index whose buffer the caller
        still needs.  Backends without donation support (CPU) silently
        ignore it.  Returns the final node's outputs as fresh buffers.

        **Mesh binding** (sharded serving): ``in_shardings`` — one
        ``jax.sharding.Sharding`` (or ``None`` = unconstrained) per external
        input, in capture order — and ``out_shardings`` — one per graph
        output — compile the fused computation under that placement
        (GSPMD partitions it across the shardings' mesh).  A cached graph
        stays pure compiled code under any mesh binding: each distinct
        (donate, shardings) combination gets its own jitted executable in
        the graph's jit cache, so one entry serves single-device workers
        and :class:`~repro.serve.sharded.ShardedWorker`\\ s side by side.
        Kernels are pure and the batch rows independent, so a data-parallel
        binding can never change functional results.  ``per_device=True``
        (NamedShardings on one mesh required) runs the chain on each
        device's shards under ``jax.shard_map`` instead of letting GSPMD
        partition it: the same result wherever every sharded dimension is
        independent rows, and the only way to run Pallas (Mosaic) kernels,
        which GSPMD cannot partition.

        **Launch-time queue binding**: per-node modeled events are appended
        to ``queue`` — the *caller's* queue — defaulting to the capture
        queue for one-shot use.  A cached graph launched by several
        workers therefore books each launch's events and modeled totals on
        the launching worker's own queue; nothing ever lands on a sibling's
        history.  The binding queue owns the WHOLE launch: for a
        multi-queue graph (:meth:`join`) the joined queues' nodes are
        booked there too — per-queue totals are per *launching* queue, not
        per device; read :meth:`modeled_breakdowns` for the per-node /
        per-device split.

        Each launch is a ``graph.launch`` profiler span
        (:func:`repro.obs.span`) carrying the graph's ``name`` and
        ``first``: 1 where this launch compiled its binding.
        """
        with span("graph.launch", graph=self.name) as sp:
            if any(q._capture is self for q in self.queues):
                raise RuntimeError("cannot launch while still capturing")
            if not self._sealed:
                raise RuntimeError(
                    "capture did not complete cleanly; re-capture the chain "
                    "before launching")
            if not any(n.out_slots for n in self.nodes):
                raise RuntimeError(
                    "cannot launch an empty CommandGraph (no kernel nodes)")
            if donate and not inputs:
                # Donating the graph's own captured arrays would poison every
                # later zero-argument launch on backends that honor donation.
                raise ValueError(
                    "donate requires explicit launch inputs: the captured "
                    "external arrays must stay valid for later launches")
            ext = list(inputs) if inputs else list(self._ext_values)
            if len(ext) != len(self._ext_slots):
                raise ValueError(
                    f"graph takes {len(self._ext_slots)} external inputs, "
                    f"got {len(ext)}")
            ext = [jnp.asarray(x) for x in ext]
            # Shape/dtype must match the capture: a silent retrace would attach
            # capture-time modeled costs to a differently-sized computation.
            for i, (x, aval) in enumerate(zip(ext, self._ext_avals)):
                if x.shape != aval.shape or x.dtype != aval.dtype:
                    raise ValueError(
                        f"launch input {i} is {x.shape}/{x.dtype}, but the "
                        f"graph was captured with {aval.shape}/{aval.dtype}; "
                        "re-capture for a different problem size")
            in_sh = None
            if in_shardings is not None:
                in_sh = tuple(in_shardings)
                if len(in_sh) != len(self._ext_slots):
                    raise ValueError(
                        f"in_shardings must cover all {len(self._ext_slots)} "
                        f"external inputs (None for unconstrained), got "
                        f"{len(in_sh)}")
            out_sh = None
            if out_shardings is not None:
                out_sh = tuple(out_shardings)
                n_out = len(self._output_slots())
                if len(out_sh) != n_out:
                    raise ValueError(
                        f"out_shardings must cover all {n_out} graph outputs "
                        f"(None for unconstrained), got {len(out_sh)}")
            donate_key = tuple(sorted(int(i) for i in donate))
            if donate_key and os.environ.get("REPRO_VERIFY") == "1":
                # donation-aware sweep (memoized): a reader of a donated slot
                # off the ordered path would observe reused storage
                findings = self.verify(donate=donate_key)
                if findings:
                    from ..analyze.graph import GraphVerifyError
                    raise GraphVerifyError(findings)
            if per_device and (in_sh is None or out_sh is None):
                raise ValueError("per_device launches need in_shardings and "
                                 "out_shardings on one mesh")
            n_compiled = len(self._jit_cache)
            fn = self._fused(donate_key, in_sh, out_sh, per_device)
            # 1 where this binding was compiled (or loaded from the
            # persistent cache) by this launch
            sp.set_metadata(first=int(len(self._jit_cache) > n_compiled))
            t0 = time.perf_counter()
            with warnings.catch_warnings():
                # CPU backends warn that donated buffers were unused; donation
                # is best-effort there by design.
                warnings.filterwarnings(
                    "ignore", message=".*donated.*", category=UserWarning)
                raw = fn(*ext)
            dispatch = time.perf_counter() - t0
            outs = tuple(Buffer(r) for r in raw)
            if queue_events:
                target = queue if queue is not None else self.queue
                # Outputs belong to the node that produced them (mirrors
                # _output_slots): the last out_slot-bearing node, or — when
                # the capture ends in explicit reads — each trailing read
                # node gets its own read-back buffer.
                slot_buf = dict(zip(self._output_slots(), outs))
                for i, node in enumerate(self.nodes):
                    node_outs = tuple(slot_buf[s] for s in node.out_slots
                                      if s in slot_buf)
                    per_node = dispatch if i == 0 else 0.0
                    ev = Event(node.kernel, node_outs, node.modeled,
                               node.energy_j, per_node)
                    target._events.append(ev)
                    if target._tracer is not None:
                        target._trace_event(ev)
                    for b in node_outs:      # dataflow edge for later eager
                        b._event = ev        # consumers, same as enqueue
            return outs

    def launch_prefix(self, inputs: Sequence[Any],
                      **launch_kwargs: Any) -> Tuple[Buffer, ...]:
        """Launch with only the first ``len(inputs)`` externals replaced.

        The remaining externals keep the arrays captured at record time —
        for a pipeline graph these are the per-stage constant buffers
        (weights, coefficients), so a serving layer can feed fresh request
        data without re-threading the pipeline's parameters (this is the
        entry point ``repro.serve.GraphCache`` launches through).  Pass
        ``queue=`` to bind the launch's events and modeled totals to the
        caller's queue, and ``in_shardings=``/``out_shardings=`` to bind
        the launch to a device mesh (see :meth:`launch`; ``in_shardings``
        covers ALL externals — replaced prefix and captured constants
        alike — in capture order).
        """
        inputs = list(inputs)
        if len(inputs) > len(self._ext_values):
            raise ValueError(
                f"launch_prefix got {len(inputs)} inputs but the graph has "
                f"only {len(self._ext_values)} externals")
        donate = launch_kwargs.get("donate", ())
        if any(int(i) >= len(inputs) for i in donate):
            # Positions beyond the replaced prefix are filled from the
            # graph's own captured arrays — donating one would consume a
            # buffer every later launch still needs (same hazard the
            # donate-without-inputs guard in launch() exists for).
            raise ValueError(
                "launch_prefix can only donate caller-supplied positions "
                f"(< {len(inputs)}); the rest are captured externals")
        return self.launch(*inputs, *self._ext_values[len(inputs):],
                           **launch_kwargs)


class _GraphJoin:
    """Context manager adding a second queue to an active capture."""

    def __init__(self, graph: CommandGraph, queue: CommandQueue):
        self._graph = graph
        self._queue = queue
        self._attached = False

    def __enter__(self) -> CommandGraph:
        graph, queue = self._graph, self._queue
        if graph.queue._capture is not graph:
            raise RuntimeError("join() is only valid inside an active capture")
        if queue._capture is not None and queue._capture is not graph:
            raise RuntimeError("queue is already capturing another graph")
        # Only detach on exit what THIS join attached: joining a queue that
        # is already capturing the graph (the capture's own queue, or a
        # nested join) must not end its capture when the inner block closes.
        self._attached = queue._capture is None
        queue._capture = graph
        if all(q is not queue for q in graph.queues):
            graph.queues.append(queue)
        return graph

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._attached and self._queue._capture is self._graph:
            self._queue._capture = None


class Device:
    """One compute device: an e-GPU instance or the scalar host baseline."""

    def __init__(self, config: EGPUConfig = EGPU_16T):
        self.config = config

    @property
    def is_host(self) -> bool:
        return self.config.name == HOST.name


class Context:
    def __init__(self, device: Device):
        self.device = device

    def create_buffer(self, data, flags: str = "rw",
                      copy: Optional[bool] = None,
                      use_host_ptr: bool = False) -> Buffer:
        """clCreateBuffer analogue.

        ``copy=None`` (default) picks the cheap path per input: a
        ``jax.Array`` is adopted as-is (it already lives in the unified
        memory — copying it again would be pure waste), anything else is
        converted.  ``copy=True`` forces a fresh device array
        (CL_MEM_COPY_HOST_PTR); ``copy=False`` requires a ``jax.Array`` and
        guarantees adoption.  ``use_host_ptr=True`` is the
        CL_MEM_USE_HOST_PTR analogue: the buffer *aliases* the caller's
        array (same object — exact under unified memory and immutable
        arrays); it implies ``copy=False`` and rejects non-JAX data, whose
        storage TinyCL could not alias.
        """
        if use_host_ptr:
            if copy:
                raise ValueError("use_host_ptr=True is incompatible with "
                                 "copy=True (CL_MEM_USE_HOST_PTR aliases "
                                 "the host array)")
            copy = False
        if copy is None:
            copy = not isinstance(data, jax.Array)
        if not copy:
            if not isinstance(data, jax.Array):
                if use_host_ptr:
                    raise TypeError(
                        "use_host_ptr requires a jax.Array host pointer, "
                        f"got {type(data).__name__}")
                raise TypeError(
                    f"copy=False requires a jax.Array, got "
                    f"{type(data).__name__} (TinyCL cannot adopt foreign "
                    "storage without a copy)")
            return Buffer(data, flags)
        arr = jnp.array(data) if isinstance(data, jax.Array) else jnp.asarray(data)
        return Buffer(arr, flags)
