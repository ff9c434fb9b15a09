"""Helpers of the benchmark's own tests: a benchmark tree in a temporary
directory with small cells that the CPU can run.

The tests run with ``JAX_PLATFORMS=cpu`` and import nothing that loads the
TPU's library at import time.  Importing this module puts the repository
and its ``src`` on ``sys.path``."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a Qwen2-shaped model small enough for the CPU
TINY_LM = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2,
               vocab_size=2048, initializer_range=0.08)


def make_tree(tmp: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark's data files with two small cells added as
    files: ``tiny.chat`` (the qwen2 family) and ``tinybio.small``."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp / "chipbench"
    cfg = json.loads((b / "configs" / "qwen2.5-3b.json").read_text())
    cfg.update(TINY_LM)
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["prompt"].update(round_up=[16, 32], min=4)
    mix["output"].update(median=8, min=2, max=24)
    (b / "traffic" / "tinychat.json").write_text(json.dumps(mix))
    (b / "workloads" / "tiny.chat.json").write_text(json.dumps(
        {"slots": 4, "max_len": 64, "rate": 20.0, "check_tokens": 48,
         "limits": {"logit_gap": 0.05}}))
    (b / "traffic" / "smallbatch.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8, "block": 16, "pool": 4}))
    (b / "workloads" / "tinybio.small.json").write_text(json.dumps(
        {"max_batch": 4, "limits": {"answer_err": 1e-5}}))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": list(TINY_LM), "why": "test"})
    bench["workloads"] += [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tinychat",
         "chips": 1, "why": "test"},
        {"name": "tinybio.small", "config": "tinybio",
         "traffic": "smallbatch", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if "qwen2.5-3b.chat" in cells:
            cells.append("tiny.chat")
        if "tinybio.batch" in cells:
            cells.append("tinybio.small")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


def run_cell(tree, name, seed=3, seconds=1.5, trace=False, **kw):
    from chipbench.bench import harness

    cell = harness.load_cell(name, tree)
    return harness.run(cell, seed, seconds, trace, t_start=0.0, root=tree,
                       require_chip=False, device_kind="TPU v5 lite", **kw)
