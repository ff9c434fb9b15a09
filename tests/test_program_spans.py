"""The served path's profiler spans (``repro.obs.span``), read back from a
profiler trace the way the benchmark reads a chip run's.

A tiny decode engine serves three requests on two slots through
``Server.submit_decode`` / ``stream``, and the TinyBio pipeline serves
three recordings through ``Server.submit`` / ``flush`` / ``result``, both
inside one profiler session.  The CPU trace has no device plane; its host
plane holds the spans.  They must name every layer boundary, nest as the
calls do, and carry the attributes the per-layer readings use.
"""

import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.apps.tinybio import TINYBIO_WORKLOAD, tinybio_stages
from repro.configs import ARCHS
from repro.core import EGPU_16T
from repro.models import init_params, model_spec
from repro.serve import DecodeEngine, QueueWorker, Server

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.bench import program  # noqa: E402

PROMPT, NEW, SLOTS = 12, 4, 2
TINYBIO = "fir+delineate_keep+fft_features+svm"
#: every span of the served path, by the call that writes it
SPANS = ("server.submit_decode", "engine.prefill", "engine.insert",
         "server.step", "engine.generate", "engine.readback",
         "graph.launch", "graph.capture", "server.submit", "batch.form",
         "dispatch.launch", "server.finalize")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Both fronts served from cold inside one profiler session; returns
    (program spans, the engine, the TinyBio server)."""
    cfg = ARCHS["qwen2.5-3b"].reduced()
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(0, cfg.vocab, (3, PROMPT))
    eng = DecodeEngine(cfg, params, num_slots=SLOTS,
                       max_len=PROMPT + NEW + 1)
    lm = Server((), workers=(), engine=eng)
    stages, _ = tinybio_stages(EGPU_16T, 0, use_pallas=False)
    bio = Server(stages, workers=(QueueWorker(EGPU_16T, name="bio"),),
                 bucket_sizes=(TINYBIO_WORKLOAD["n"],), max_batch=2)
    x = np.random.default_rng(2).standard_normal(
        (3, TINYBIO_WORKLOAD["n"])).astype(np.float32)

    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        rids = [lm.submit_decode(p, max_new=NEW) for p in prompts]
        streamed = [list(lm.stream(r)) for r in rids]
        bio_rids = [bio.submit(x[i]) for i in range(3)]   # 2 launch now
        bio.flush()
        answers = [np.asarray(bio.result(r)[0]) for r in bio_rids]
    finally:
        jax.profiler.stop_trace()
    assert all(len(t) == NEW for t in streamed) and len(answers) == 3
    return program.load(str(out), []), eng, bio


def _named(prog, name):
    return [s for s in prog.spans if s.name == name]


def _parent(prog, s):
    """The innermost span that encloses ``s``."""
    outer = [p for p in prog.spans if p is not s
             and p.t0 <= s.t0 and s.t1 <= p.t1]
    return max(outer, key=lambda p: (p.t0, -p.t1), default=None)


@pytest.mark.parametrize("name", SPANS)
def test_every_layer_boundary_writes_its_span(served, name):
    prog, _, _ = served
    assert _named(prog, name), name
    assert program.PROGRAM.match(name)


def test_a_step_holds_the_generate_which_holds_launch_and_readback(served):
    prog, eng, _ = served
    gens = _named(prog, "engine.generate")
    assert len(gens) == eng.n_steps
    for g in gens:
        assert _parent(prog, g).name == "server.step"
        assert g.stats["occupied"] >= 1
        inside = [s.name for s in prog.spans
                  if s is not g and _parent(prog, s) is g]
        assert "engine.readback" in inside and "graph.launch" in inside
    for r in _named(prog, "engine.readback"):
        assert _parent(prog, r).name == "engine.generate"


def test_prefill_carries_its_request_and_its_wait_for_a_slot(served):
    prog, eng, _ = served
    pre = _named(prog, "engine.prefill")
    assert len(pre) == eng.n_prefills == 3
    assert sorted(s.stats["rid"] for s in pre) == sorted(
        {s.stats["rid"] for s in _named(prog, "server.submit_decode")})
    assert all(s.stats["prompt_len"] == PROMPT for s in pre)
    waits = [s.stats["wait_us"] for s in pre]
    assert min(waits) >= 0
    # the third request waited in the queue until a slot freed
    assert max(waits) > 0
    for s in _named(prog, "engine.insert"):
        assert 0 <= s.stats["slot"] < SLOTS


def test_insert_carries_the_state_bytes_it_writes(served):
    """Each insert writes one slot's whole decode state, every kind."""
    prog, eng, _ = served
    one_slot = sum(eng.slot_state_bytes().values()) // SLOTS
    inserts = _named(prog, "engine.insert")
    assert inserts and one_slot > 0
    assert all(s.stats["state_bytes"] == one_slot for s in inserts)
    assert sum(eng.state_written_bytes.values()) == one_slot * len(inserts)


def test_launch_names_its_graph_and_marks_the_compile(served):
    prog, _, _ = served
    launches = _named(prog, "graph.launch")
    by_graph = {}
    for s in launches:
        by_graph.setdefault(s.stats["graph"], []).append(s.stats["first"])
    assert {"engine.generate", "engine.prefill", TINYBIO} <= set(by_graph)
    for firsts in (by_graph["engine.generate"], by_graph[TINYBIO]):
        assert firsts[0] == 1 and set(firsts[1:]) == {0}, firsts
    captured = {s.stats["graph"] for s in _named(prog, "graph.capture")}
    assert captured == set(by_graph)


def test_pipeline_spans_count_requests_and_launches(served):
    prog, _, bio = served
    assert [s.stats["n"] for s in _named(prog, "batch.form")] == [2, 1]
    launch = _named(prog, "dispatch.launch")
    assert [s.stats["n"] for s in launch] == [2, 1]
    assert {s.stats["lane"] for s in launch} == {"bio"}
    fin = _named(prog, "server.finalize")
    assert sum(s.stats["tickets"] for s in fin) == 2
    assert sum(s.stats["n"] for s in fin) == 3 == bio.n_completed
    assert len(_named(prog, "server.submit")) == 3


def test_generate_program_is_named_after_its_graph(served):
    _, eng, _ = served
    graph = eng.decode_graph
    assert graph.name == "engine.generate"
    (fn,) = graph._jit_cache.values()
    text = fn.lower(*graph.ext_avals).as_text()
    assert "module @jit_engine.generate" in text
    assert "jit_run" not in text
