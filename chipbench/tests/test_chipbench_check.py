"""The correctness check fails the control and every fault a cell can
have: the harness drives the rest of a run with the timed path broken
underneath, and ``correct`` comes out false."""

import numpy as np
import pytest

import benchtree
from benchtree import run_cell


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchtree.make_tree(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lm_control_fails_the_limit(tree, seed):
    """float8 in the program's place reads above the logit-gap limit, and
    the run comes out not correct; the program's own reading passes."""
    res = run_cell(tree, "tiny.chat", seed=seed, control=True)
    c = res["compared"]
    assert not res["correct"], c
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]
    assert c["program_logit_gap"]["value"] < c["logit_gap"]["limit"]
    assert "control_logit_gap" not in c


def test_lm_token_altered_where_produced(tree):
    def plant(bench):
        eng = bench.engine
        generate = eng.generate

        def altered(params, state):
            state, toks = generate(params, state)
            if eng.n_steps % 5 == 0:
                toks = toks.copy()
                toks[:] = (toks + 1) % bench.cfg["vocab_size"]
            return state, toks
        eng.generate = altered

    res = run_cell(tree, "tiny.chat", seed=2, prepare=plant)
    assert not res["correct"]
    assert res["compared"]["logit_gap"]["value"] > \
        res["compared"]["logit_gap"]["limit"]


def test_pipeline_control_fails_the_limit(tree):
    res = run_cell(tree, "tinybio.small", seed=3, seconds=1.0, control=True)
    c = res["compared"]
    assert not res["correct"], c
    assert c["answer_err"]["value"] > c["answer_err"]["limit"]
    assert c["program_answer_err"]["value"] < c["answer_err"]["limit"]


def _crop_fault(monkeypatch, fn):
    """A ``prepare`` hook that breaks the batch crop once set-up is done."""
    from repro.serve.batching import MicroBatch

    crop = MicroBatch.crop

    def plant(bench):
        monkeypatch.setattr(MicroBatch, "crop",
                            lambda self, outputs: fn(crop(self, outputs)))
    return plant


def test_pipeline_answer_altered(tree, monkeypatch):
    plant = _crop_fault(monkeypatch, lambda rows: [
        (rows[0][0] + 1.0,) + tuple(rows[0][1:])] + list(rows[1:]))
    res = run_cell(tree, "tinybio.small", seed=4, seconds=1.0, prepare=plant)
    assert not res["correct"]
    assert res["compared"]["answer_err"]["value"] > 1e-3


def test_pipeline_half_the_batch_left_out(tree, monkeypatch):
    plant = _crop_fault(monkeypatch, lambda rows: rows[: max(1, len(rows) // 2)])
    res = run_cell(tree, "tinybio.small", seed=4, seconds=1.0, prepare=plant)
    assert not res["correct"]
    assert res["compared"]["missing"]["value"] > 0
    assert res["failed"] > 0
    assert np.isfinite(res["compared"]["answer_err"]["value"])
