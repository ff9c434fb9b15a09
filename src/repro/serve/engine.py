"""Slot-based continuous-batching decode engine (ISSUE 9).

The maxtext/JetStream serving split, on TinyCL primitives:

- :meth:`DecodeEngine.prefill` runs one request's prompt through a cached
  per-prompt-length ``CommandGraph`` and returns a :class:`Prefix` — the
  first greedy token plus the request's batch-1 cache.
- :meth:`DecodeEngine.insert` splices a prefix into slot ``i`` of a
  persistent :class:`DecodeState` whose cache leaves live batch-``num_slots``
  wide on the owning worker's queue.
- :meth:`DecodeEngine.generate` advances ALL occupied slots one token in
  exactly ONE cached-graph launch per step; freed slots admit freshly
  prefilled requests between steps, so a finished request never blocks its
  neighbors.

Engine invariants (pinned by ``tests/test_decode_serve.py``):

- **One cached graph per generate step.**  The step graph is captured once
  per (model config, num_slots) and re-launched with
  ``launch_prefix(..., donate=<cache leaves>)`` — slot insertion is a
  launch-time buffer update, never a re-capture, and the graphs stay pure:
  slot state is data the launch carries, not state the capture holds.
- **Slot insertion never perturbs other slots' outputs.**  The step runs
  every slot at its own position (a positions vector) and each slot
  reads and writes only its own cache rows; decode under staggered arrival
  is bit-identical to whole-batch
  :func:`~repro.train.serve.greedy_generate` for every cache family (plain
  KV, MLA latent, rwkv6 O(1) state, and jamba's mamba conv and SSM state
  beside its KV rows).
- **No full-vocabulary output rides the step graph.**  The decode kernel
  takes the greedy argmax inside the step;
  :meth:`DecodeEngine.decode_graph`'s out avals carry tokens + cache (and
  a MoE model's routed experts) only (aval-checked at capture).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.apu import Stage
from ..core.device import EGPUConfig, EGPU_16T
from ..core.machine import WorkCounts
from ..core.program import KernelRegistry, Program, kernel_family
from ..core.runtime import CommandGraph, Kernel
from ..core.scheduler import optimal_ndrange
from ..models.config import ModelConfig
from ..models.moe import serve_row_tile
from ..models.transformer import (cache_axes, cache_kinds, cache_struct,
                                  decode_step, moe_layers, prefill)
from ..obs import Tracer
from ..obs.profiler import span
from .batching import MicroBatch
from .cache import GraphCache
from .dispatch import QueueWorker

_TOKEN_BYTES = 4                     # int32 token / position ids


def _is_axes(x: Any) -> bool:
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def batch_axes(cfg: ModelConfig):
    """Pytree (cache structure) of each leaf's batch-axis index.

    Derived from :func:`~repro.models.transformer.cache_axes` — stacked
    ``pos{i}`` leaves carry batch at axis 1 behind the leading "layers"
    axis, deepseek's dense ``layer0`` leaves at axis 0 — so the engine
    never hard-codes a layout the model family can vary.
    """
    return jax.tree_util.tree_map(lambda ax: ax.index("batch"),
                                  cache_axes(cfg), is_leaf=_is_axes)


def _engine_counts(*, batch: int, params_bytes: float, cache_bytes: float,
                   write_bytes: float, ops: float,
                   io_bytes: float) -> WorkCounts:
    """First-order structural work of one engine step (or prefill): the
    decode state stays on the lane, so only token / position I/O crosses
    the host bus while the params + cache stream through the D$
    hierarchy."""
    return WorkCounts(
        ops=float(ops),
        dcache_bytes=float(params_bytes) + float(cache_bytes)
        + float(write_bytes),
        host_bytes=float(io_bytes),
        working_set=float(params_bytes) + float(cache_bytes))


#: engine kernel families live in a PRIVATE registry: their builders
#: require a ModelConfig (no default variant exists), so they must not
#: pollute the global registry that ``Program.create_kernels()`` sweeps
ENGINE_REGISTRY = KernelRegistry()


@kernel_family("engine.prefill", registry=ENGINE_REGISTRY)
def build_prefill_kernel(config: EGPUConfig = EGPU_16T, *,
                         cfg: ModelConfig, max_len: int,
                         cache_dtype: str = "bfloat16") -> Kernel:
    """Batch-1 prompt pass -> (first greedy token (1,), *cache leaves[,
    routed experts (S, L_moe, top_k)]).

    One kernel serves every prompt length — the per-length specialization
    lives in the :class:`~repro.serve.cache.GraphCache` key (input avals),
    so distinct lengths get distinct captured graphs of the same kernel.
    A model with MoE layers adds one output: each prompt token's routed
    experts in every MoE layer.
    """
    dtype = jnp.dtype(cache_dtype)
    moe = moe_layers(cfg) > 0

    # ``_params_def`` (the params treedef) is stamped on the executor by the
    # engine before first use — builders only see hashable variant keys, and
    # the treedef is identical for every engine sharing this (cfg, variant).
    def engine_prefill(prompt, *param_leaves):
        params = jax.tree_util.tree_unflatten(
            engine_prefill._params_def, param_leaves)
        logits, cache, *experts = prefill(params, {"tokens": prompt}, cfg,
                                          max_len, dtype,
                                          return_experts=moe)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (tok, *jax.tree_util.tree_leaves(cache),
                *(jnp.swapaxes(e[:, 0], 0, 1) for e in experts))

    return Kernel(name="engine.prefill", executor=engine_prefill,
                  counts=_engine_counts)


@kernel_family("engine.generate", registry=ENGINE_REGISTRY)
def build_decode_kernel(config: EGPUConfig = EGPU_16T, *,
                        cfg: ModelConfig, num_slots: int,
                        cache_dtype: str = "bfloat16") -> Kernel:
    """One token for every slot: (tokens (B,), positions (B,), *cache,
    *params) -> (next tokens (B,), *new cache leaves[, routed experts (B,
    L_moe, top_k)]).

    The whole batch runs as one :func:`~repro.models.transformer.decode_step`
    with each slot's own position, and every layer writes only each slot's
    new cache row in place — per-slot positions are what make staggered
    insertion bit-identical to each request's own whole-batch trajectory,
    and the donated cache is never rebuilt or copied.  The step takes its
    greedy argmax inside, so no ``(B, vocab)`` buffer rides the captured
    graph's outputs.  A model with MoE layers adds one output: each slot's
    routed experts in every MoE layer.
    """
    del num_slots                        # identity only: one graph per width
    cache_def = jax.tree_util.tree_structure(batch_axes(cfg))
    n_cache = cache_def.num_leaves
    moe = moe_layers(cfg) > 0

    def engine_decode(tokens, positions, *state):
        cache = jax.tree_util.tree_unflatten(cache_def, state[:n_cache])
        params = jax.tree_util.tree_unflatten(
            engine_decode._params_def, state[n_cache:])
        logits, new_cache, *experts = decode_step(
            params, cache, tokens, positions, cfg, return_experts=moe)
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (toks, *jax.tree_util.tree_leaves(new_cache),
                *(jnp.swapaxes(e[:, :, 0], 0, 1) for e in experts))

    return Kernel(name="engine.generate", executor=engine_decode,
                  counts=_engine_counts)


@dataclasses.dataclass
class Prefix:
    """One prefilled request, ready for :meth:`DecodeEngine.insert`."""

    token: jax.Array                     # (1,) int32 — first greedy token
    cache: Any                           # batch-1 cache pytree
    pos: int                             # next decode position (= prompt len)
    prompt_len: int
    rid: Optional[int] = None            # server request id (None standalone)


@dataclasses.dataclass
class DecodeState:
    """The persistent batched decode state (all ``num_slots`` wide).

    ``tokens``/``cache`` are replaced by each :meth:`DecodeEngine.generate`
    launch's outputs (the cache leaves are *donated*, so the old leaves are
    consumed in place); ``positions``/``occupied``/``rids`` are host-side
    launch-time data.
    """

    tokens: jax.Array                    # (B,) int32 — last emitted per slot
    positions: jax.Array                 # (B,) int32 — next decode position
    cache: Any                           # batch-B cache pytree
    occupied: List[bool]
    rids: List[Optional[int]]

    @property
    def num_slots(self) -> int:
        return len(self.occupied)

    @property
    def n_occupied(self) -> int:
        return sum(self.occupied)

    def free_slots(self) -> List[int]:
        return [i for i, o in enumerate(self.occupied) if not o]


class DecodeEngine:
    """Continuous-batching decode on one :class:`QueueWorker` lane.

    ::

        engine = DecodeEngine(cfg, params, num_slots=4, max_len=64)
        state = engine.init_state()
        state = engine.insert(engine.prefill(params, prompt), state, slot=0)
        state, toks = engine.generate(params, state)   # ONE graph launch

    The worker must capture WITHOUT explicit transfers: the decode state is
    resident — donated back to each launch, never round-tripped — and the
    counts model prices exactly token/position I/O as host traffic.

    Donation discipline: the step writes each slot's new cache row into
    the donated cache leaves in place, so each launch's cache outputs ARE
    its inputs' buffers.  Every launch therefore realizes its token output
    and retires (drains) before the next launch donates the buffers the
    previous outputs alias.
    """

    def __init__(self, cfg: ModelConfig, params: Any, *,
                 num_slots: int = 4, max_len: int = 64,
                 config: EGPUConfig = EGPU_16T,
                 worker: Optional[QueueWorker] = None,
                 cache: Optional[GraphCache] = None,
                 cache_dtype: Any = jnp.bfloat16,
                 tracer: Optional[Tracer] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 name: str = "engine"):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if cfg.is_encoder:
            raise ValueError(f"{cfg.name} is encoder-only: no decode engine")
        self.cfg = cfg
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.cache_dtype = jnp.dtype(cache_dtype)
        self.name = name
        self.worker = worker if worker is not None else QueueWorker(
            config, name=name, max_in_flight=1, explicit_transfers=False,
            clock=clock, tracer=tracer)
        if self.worker.apu.explicit_transfers:
            raise ValueError(
                "DecodeEngine needs a worker with explicit_transfers=False: "
                "the decode state is resident (donated in place), not "
                "round-tripped through transfer nodes every step")
        self.config = self.worker.apu.egpu.config
        self.cache = cache if cache is not None else GraphCache(capacity=16)
        self.clock = clock
        self._program = Program.build(self.config, registry=ENGINE_REGISTRY)
        self._bidx = batch_axes(cfg)
        self._param_leaves = tuple(jax.tree_util.tree_leaves(params))
        self._params_bytes = float(sum(x.nbytes for x in self._param_leaves))
        self._param_elems = float(sum(x.size for x in self._param_leaves))
        # per-slot cache traffic: kv_seq-indexed leaves write one position
        # per step, recurrent (O(1)) leaves rewrite whole; reads sweep all
        slot_struct = cache_struct(cfg, 1, max_len, self.cache_dtype)
        axes_leaves = jax.tree_util.tree_leaves(cache_axes(cfg),
                                                is_leaf=_is_axes)
        struct_leaves = jax.tree_util.tree_leaves(slot_struct)

        def _nbytes(s):
            return float(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize

        self._slot_cache_bytes = float(
            sum(_nbytes(x) for x in struct_leaves))
        self._slot_write_bytes = float(sum(
            (_nbytes(x) / max_len if "kv_seq" in ax else _nbytes(x))
            for x, ax in zip(struct_leaves, axes_leaves)))
        #: what each cache leaf holds ("kv", "conv", "ssm", ...), in leaf
        #: order
        self._kinds = tuple(jax.tree_util.tree_leaves(cache_kinds(cfg)))
        #: bytes that inserts wrote into the slots, by kind
        self.state_written_bytes: Dict[str, int] = {}
        self._decode_stage: Optional[Tuple[Stage, ...]] = None
        self._prefill_stages: Dict[int, Tuple[Stage, ...]] = {}
        self._canonical_structs: Optional[Tuple[Any, ...]] = None
        #: the captured per-step graph (None until the first generate) —
        #: tests pin the no-(B, vocab)-output invariant on its out_avals
        self.decode_graph: Optional[CommandGraph] = None
        # accounting
        self.n_prefills = 0
        self.n_inserts = 0
        self.n_steps = 0
        self.n_tokens = 0                # tokens emitted from occupied slots
        self._occupancy_sum = 0.0
        # MoE: each launch's routed experts, counted on the host after the
        # readback the launch already makes
        self._moe = moe_layers(cfg) > 0
        self.moe_rows_routed = 0         # rows routed to held experts
        self.moe_rows_computed = 0       # rows the grouped matmuls computed
        self.moe_experts_touched = 0     # (layer, held expert) pairs with rows
        #: the last launch's routed experts (tokens, L_moe, top_k), global
        #: ids: a prefill's prompt positions, a step's slots
        self.moe_last_experts: Optional[np.ndarray] = None
        #: and its rows per MoE layer and held expert (L_moe, E_held)
        self.moe_last_rows: Optional[np.ndarray] = None

    # -- state construction -------------------------------------------------
    def _decode_kernel(self) -> Kernel:
        kern = self._program.create_kernel(
            "engine.generate", cfg=self.cfg, num_slots=self.num_slots,
            cache_dtype=str(self.cache_dtype))
        kern.executor._params_def = jax.tree_util.tree_structure(self.params)
        return kern

    def _prefill_kernel(self) -> Kernel:
        kern = self._program.create_kernel(
            "engine.prefill", cfg=self.cfg, max_len=self.max_len,
            cache_dtype=str(self.cache_dtype))
        kern.executor._params_def = jax.tree_util.tree_structure(self.params)
        return kern

    def _cache_structs(self) -> Tuple[Any, ...]:
        """Canonical per-leaf avals of the persistent cache: the prefill's
        OWN cache avals, ``num_slots`` wide, not ``cache_struct``'s
        advertised ones — recurrent families emit some state at the
        activation dtype (rwkv's token-shift state), the decode step
        carries every leaf at the dtype it is given, and so a state seeded
        as prefill emits it takes each insert exactly and keeps every step
        on ONE captured graph."""
        if self._canonical_structs is not None:
            return self._canonical_structs
        pstructs = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                    for p in self._param_leaves]
        outs = jax.eval_shape(self._prefill_kernel().executor,
                              jax.ShapeDtypeStruct((1, 1), jnp.int32),
                              *pstructs)
        n_cache = len(jax.tree_util.tree_leaves(self._bidx))
        self._canonical_structs = tuple(
            jax.ShapeDtypeStruct(o.shape[:i] + (self.num_slots,)
                                 + o.shape[i + 1:], o.dtype)
            for o, i in zip(outs[1:1 + n_cache],
                            jax.tree_util.tree_leaves(self._bidx)))
        return self._canonical_structs

    def slot_state_bytes(self) -> Dict[str, int]:
        """Bytes of the decode state the ``num_slots`` slots hold, by kind:
        a hybrid's KV rows beside its mamba conv windows and SSM states."""
        out: Dict[str, int] = {}
        for s, kind in zip(self._cache_structs(), self._kinds):
            out[kind] = out.get(kind, 0) + s.size * s.dtype.itemsize
        return out

    def init_state(self) -> DecodeState:
        """An all-free decode state (zero cache, batch ``num_slots``)."""
        b = self.num_slots
        cache = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._bidx),
            [jnp.zeros(s.shape, s.dtype) for s in self._cache_structs()])
        return DecodeState(
            tokens=jnp.zeros((b,), jnp.int32),
            positions=jnp.zeros((b,), jnp.int32),
            cache=cache, occupied=[False] * b, rids=[None] * b)

    def _count_moe(self, experts: np.ndarray) -> None:
        """Add one launch's routed experts (tokens, L_moe, top_k) to the
        counters; the grouped matmul computes each touched held expert's
        rows in whole tiles of the row tile it used for that many tokens."""
        cfg = self.cfg
        held = experts - cfg.expert_first
        held = np.where((held >= 0) & (held < cfg.experts_held), held,
                        cfg.experts_held)
        rows = np.stack([np.bincount(held[:, i].ravel(),
                                     minlength=cfg.experts_held + 1)
                         for i in range(held.shape[1])])[:, :-1]
        tm = serve_row_tile(cfg, experts.shape[0])
        self.moe_last_experts, self.moe_last_rows = experts, rows
        self.moe_rows_routed += int(rows.sum())
        self.moe_rows_computed += int((-(-rows // tm) * tm).sum())
        self.moe_experts_touched += int((rows > 0).sum())

    # -- counts -------------------------------------------------------------
    def _decode_counts_params(self) -> Dict[str, Any]:
        b = self.num_slots
        return dict(
            batch=b,
            params_bytes=self._params_bytes,
            cache_bytes=self._slot_cache_bytes * b,
            write_bytes=self._slot_write_bytes * b,
            ops=self._param_elems * b,
            io_bytes=float(3 * b * _TOKEN_BYTES))   # tokens+pos in, tokens out

    def _prefill_counts_params(self, prompt_len: int) -> Dict[str, Any]:
        return dict(
            batch=1,
            params_bytes=self._params_bytes,
            cache_bytes=self._slot_cache_bytes,
            write_bytes=self._slot_write_bytes * prompt_len,
            ops=self._param_elems * prompt_len,
            io_bytes=float(prompt_len * _TOKEN_BYTES + _TOKEN_BYTES))

    # -- graphs -------------------------------------------------------------
    def _prefill_graph(self, prompt: jax.Array) -> CommandGraph:
        s = int(prompt.shape[1])
        stages = self._prefill_stages.get(s)
        if stages is None:
            stages = (Stage(self._prefill_kernel(),
                            counts_params=self._prefill_counts_params(s)),)
            self._prefill_stages[s] = stages
        inputs = (prompt, *self._param_leaves)
        ndr = [optimal_ndrange(s * self.cfg.d_model, self.config)]
        graph, _hit = self.cache.get_or_capture(
            self.worker.apu, list(stages), inputs, ndranges=ndr)
        return graph

    def _generate_graph(self, state: DecodeState) -> CommandGraph:
        stages = self._decode_stage
        if stages is None:
            stages = (Stage(self._decode_kernel(),
                            counts_params=self._decode_counts_params()),)
            self._decode_stage = stages
        inputs = (state.tokens, state.positions,
                  *jax.tree_util.tree_leaves(state.cache),
                  *self._param_leaves)
        ndr = [optimal_ndrange(self.num_slots * self.cfg.d_model,
                               self.config)]
        graph, hit = self.cache.get_or_capture(
            self.worker.apu, list(stages), inputs, ndranges=ndr)
        if not hit:
            # the satellite-6 invariant, checked at capture: no output aval
            # is a full-vocabulary (B, Vp) logits buffer
            bad = [a for a in graph.out_avals
                   if len(a.shape) >= 2
                   and a.shape[-1] == self.cfg.vocab_padded
                   and a.shape[0] == self.num_slots]
            if bad:
                raise AssertionError(
                    f"generate-step graph carries full-vocab outputs "
                    f"{[(a.shape, str(a.dtype)) for a in bad]}; "
                    "the engine's decode step must elide them")
            # donation-aware sanitizer sweep at capture time (repro.analyze):
            # steady-state launches donate the cache-leaf slots, so prove
            # NOW that every reader of those slots sits on the ordered path
            # to the realize-then-drain boundary.  Memoized on the graph —
            # the per-step donating launch re-checks for free.
            donate = tuple(range(
                2, 2 + len(jax.tree_util.tree_leaves(state.cache))))
            findings = graph.verify(donate=donate)
            self.cache.findings += len(findings)
            if findings and os.environ.get("REPRO_VERIFY") == "1":
                from ..analyze.graph import GraphVerifyError
                raise GraphVerifyError(findings)
        self.decode_graph = graph
        return graph

    # -- the JetStream-style API -------------------------------------------
    def prefill(self, params: Optional[Any], prompt: Any,
                rid: Optional[int] = None,
                wait_us: Optional[float] = None) -> Prefix:
        """Run one request's prompt; returns its :class:`Prefix`.

        ``params`` may be ``None`` to use the engine's bound params (they
        are launch inputs either way — the captured graph is pure).  The
        call is one ``engine.prefill`` profiler span carrying ``rid``, the
        prompt's length and ``wait_us``, the time the request waited for a
        slot (the server passes it).
        """
        if params is not None and params is not self.params:
            raise ValueError(
                "prefill params must be the engine's bound params: the "
                "captured graphs pin their avals (pass None to reuse)")
        attrs = {k: v for k, v in (("rid", rid), ("wait_us", wait_us))
                 if v is not None}
        with span("engine.prefill", **attrs) as sp:
            prompt = jnp.asarray(prompt, jnp.int32)
            if prompt.ndim == 1:
                prompt = prompt[None, :]
            if prompt.ndim != 2 or prompt.shape[0] != 1:
                raise ValueError(
                    f"prefill takes ONE request's prompt (S,) or (1, S); got "
                    f"shape {tuple(prompt.shape)}")
            s = int(prompt.shape[1])
            sp.set_metadata(prompt_len=s)
            if s < 1 or s >= self.max_len:
                raise ValueError(f"prompt length {s} must be in "
                                 f"[1, max_len={self.max_len})")
            graph = self._prefill_graph(prompt)
            batch = MicroBatch(bucket_key=("engine.prefill", s),
                               inputs=(prompt, *self._param_leaves),
                               requests=(), capacity=1, crop_outputs=False)
            ticket, _ = self.worker.launch(graph, batch, t_now=self.clock())
            outs = [b.data for b in ticket.outputs]
            experts = outs.pop() if self._moe else None
            tok = outs[0]
            cache = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self._bidx), outs[1:])
            jax.block_until_ready(tok)
            if experts is not None:
                self._count_moe(np.asarray(jax.device_get(experts)))
            self.worker.drain()
            self.n_prefills += 1
            return Prefix(token=tok, cache=cache, pos=s, prompt_len=s, rid=rid)

    def insert(self, prefix: Prefix, state: DecodeState,
               slot: int) -> DecodeState:
        """Splice ``prefix`` into ``slot`` — a launch-time buffer update on
        the persistent state, never a re-capture; one ``engine.insert``
        profiler span."""
        if not 0 <= slot < state.num_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {state.num_slots})")
        if state.occupied[slot]:
            raise ValueError(f"slot {slot} is occupied (rid="
                             f"{state.rids[slot]}); release it first")
        written = {k: v // self.num_slots
                   for k, v in self.slot_state_bytes().items()}
        with span("engine.insert", slot=slot,
                  state_bytes=sum(written.values())):
            state.tokens = state.tokens.at[slot].set(prefix.token[0])
            state.positions = state.positions.at[slot].set(prefix.pos)
            state.cache = jax.tree_util.tree_map(
                lambda dst, src, i: jax.lax.dynamic_update_index_in_dim(
                    dst, jnp.squeeze(src, axis=i).astype(dst.dtype), slot, i),
                state.cache, prefix.cache, self._bidx)
        state.occupied[slot] = True
        state.rids[slot] = prefix.rid
        self.n_inserts += 1
        for kind, n in written.items():
            self.state_written_bytes[kind] = (
                self.state_written_bytes.get(kind, 0) + n)
        return state

    def release(self, state: DecodeState, slot: int) -> DecodeState:
        """Free a finished slot (its lane keeps stepping on stale data —
        pure and discarded — until a fresh prefix is inserted)."""
        state.occupied[slot] = False
        state.rids[slot] = None
        return state

    def generate(self, params: Optional[Any], state: DecodeState
                 ) -> Tuple[DecodeState, np.ndarray]:
        """Advance every slot one token — ONE cached-graph launch.

        Returns ``(state, tokens)`` where ``tokens`` is the realized (B,)
        int32 next-token vector (occupied slots' entries are live; free
        slots' entries are stale lanes to ignore).  The call is one
        ``engine.generate`` profiler span; reading the tokens back and
        waiting for the new cache is its ``engine.readback`` child.
        """
        if params is not None and params is not self.params:
            raise ValueError(
                "generate params must be the engine's bound params: the "
                "captured graph pins their avals (pass None to reuse)")
        occ = state.n_occupied
        with span("engine.generate", occupied=occ):
            graph = self._generate_graph(state)
            cache_leaves = jax.tree_util.tree_leaves(state.cache)
            inputs = (state.tokens, state.positions, *cache_leaves,
                      *self._param_leaves)
            # donate exactly the persistent cache leaves (input slots 2..) so
            # XLA reuses them for the step's outputs instead of allocating a
            # fresh cache per token
            donate = tuple(range(2, 2 + len(cache_leaves)))
            batch = MicroBatch(bucket_key=("engine.generate", self.num_slots),
                               inputs=inputs, requests=(),
                               capacity=self.num_slots, crop_outputs=False,
                               donate=donate)
            ticket, _ = self.worker.launch(graph, batch, t_now=self.clock())
            outs = [b.data for b in ticket.outputs]
            experts = outs.pop() if self._moe else None
            toks = outs[0]
            new_leaves = outs[1:]
            # realize BEFORE retiring: the next launch donates these buffers
            with span("engine.readback"):
                tokens_np, experts_np = jax.device_get((toks, experts))
                tokens_np = np.asarray(tokens_np)
                jax.block_until_ready(new_leaves)
            if experts_np is not None:
                self._count_moe(np.asarray(experts_np))
            self.worker.drain()
            state.tokens = toks
            state.positions = state.positions + 1
            state.cache = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self._bidx), new_leaves)
            self.n_steps += 1
            self.n_tokens += occ
            self._occupancy_sum += occ / self.num_slots
            return state, tokens_np

    # -- reporting ----------------------------------------------------------
    @property
    def occupancy(self) -> float:
        """Mean occupied-slot fraction across generate steps."""
        return self._occupancy_sum / self.n_steps if self.n_steps else 0.0

    def stats(self) -> Dict[str, float]:
        return {
            "num_slots": self.num_slots,
            "n_prefills": self.n_prefills,
            "n_inserts": self.n_inserts,
            "n_steps": self.n_steps,
            "n_tokens": self.n_tokens,
            "occupancy": self.occupancy,
        }

    def publish_metrics(self, registry) -> None:
        """Snapshot the engine counters into a
        :class:`~repro.obs.MetricsRegistry` (idempotent set-style)."""
        c = registry.counter("repro_engine_events_total",
                             "decode-engine prefills/inserts/steps/tokens")
        c.set_total(self.n_prefills, kind="prefills")
        c.set_total(self.n_inserts, kind="inserts")
        c.set_total(self.n_steps, kind="steps")
        c.set_total(self.n_tokens, kind="tokens")
        registry.gauge("repro_engine_slots",
                       "decode-engine slot width").set(self.num_slots)
        registry.gauge("repro_engine_occupancy",
                       "mean occupied-slot fraction").set(self.occupancy)
        held = registry.gauge("repro_engine_slot_state_bytes",
                              "decode state the slots hold, by kind")
        for kind, n in self.slot_state_bytes().items():
            held.set(n, kind=kind)
        wrote = registry.counter("repro_engine_state_written_bytes_total",
                                 "slot state written by inserts, by kind")
        for kind, n in self.state_written_bytes.items():
            wrote.set_total(n, kind=kind)
        if self._moe:
            moe = registry.counter(
                "repro_moe_events_total",
                "held-expert rows routed / computed (tile padding "
                "included) and (layer, expert) pairs touched")
            moe.set_total(self.moe_rows_routed, kind="rows_routed")
            moe.set_total(self.moe_rows_computed, kind="rows_computed")
            moe.set_total(self.moe_experts_touched, kind="experts_touched")
