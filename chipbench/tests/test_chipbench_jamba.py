"""The Jamba family on the CPU: its configuration maps onto the program's
hybrid, its costs match counts made by hand, a small cell of it added as
files runs end to end and passes its check while the float8 control fails
it, and the benchmark's self-contained reference agrees with the
program's own (``repro.models.reference_jamba``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtree
from benchtree import run_cell
from chipbench.families import jamba as fam
from chipbench.reference import jamba as reference

#: Jamba's structure at small widths: one period of 14 layers, attention
#: at index 7, one key-value head for 4 query heads
TINY_JAMBA = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=1,
                  num_hidden_layers=14, mamba_dt_rank=8, vocab_size=512)


def _config(**kw):
    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "jamba2-3b.json").read_text())
    cfg.update(kw)
    cfg["name"] = "jamba2-3b"
    return cfg


def _tiny_config():
    cfg = _config(**TINY_JAMBA)
    cfg["assumed"] = dict(cfg["assumed"], initializer_range=0.1)
    cfg["name"] = "tinyjamba"
    return cfg


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    t = benchtree.make_tree(tmp_path_factory.mktemp("bench"))
    b = t / "chipbench"
    (b / "configs" / "tinyjamba.json").write_text(json.dumps(_tiny_config()))
    mix = json.loads((b / "traffic" / "longctx.json").read_text())
    mix["prompt"].update(median=24, min=8, round_up=[16, 32])
    mix["output"].update(min=2, max=8)
    mix["clients"] = 4
    (b / "traffic" / "tinyctx.json").write_text(json.dumps(mix))
    # the tiny bfloat16 model reads 0.03-0.07 against the reference, its
    # float8 control 0.43-0.74 (CPU): the limit lies between, with room
    (b / "workloads" / "tinyjamba.ctx.json").write_text(json.dumps(
        {"slots": 4, "max_len": 64, "check_tokens": 24,
         "limits": {"logit_gap": 0.15}}))
    bench = json.loads((t / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tinyjamba", "source": "test",
                             "file": "chipbench/configs/tinyjamba.json",
                             "reduced": list(TINY_JAMBA), "why": "test"})
    bench["workloads"].append({"name": "tinyjamba.ctx",
                               "config": "tinyjamba", "traffic": "tinyctx",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "jamba2-3b.longctx" in m.get("workloads", []):
            m["workloads"].append("tinyjamba.ctx")
    (t / "BENCHMARK.json").write_text(json.dumps(bench))
    return t


def test_program_config_maps_the_published_keys():
    mc = fam.program_config(_config())
    assert mc.n_layers == 28 and mc.period == 14 and mc.n_groups == 2
    assert mc.block_pattern.index("attn") == 7
    assert mc.block_pattern.count("attn") == 1
    assert set(mc.mlp_pattern) == {"dense"}
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (20, 1, 128)
    assert mc.rotary_pct == 0.0 and mc.tie_embeddings
    assert (mc.mamba_d_inner, mc.mamba_d_state, mc.mamba_d_conv,
            mc.mamba_dt_rank) == (5120, 16, 4, 160)
    assert mc.norm_eps == 1e-6 and mc.dtype == "bfloat16"
    assert mc.param_count() == 3_029_191_552
    with pytest.raises(ValueError):
        fam.program_config(_config(num_experts=16))
    with pytest.raises(ValueError):
        fam.program_config(_config(num_hidden_layers=27))


def test_costs_by_hand():
    cfg = _tiny_config()
    d, ff, di, n, dtr, h, hd = 64, 128, 128, 16, 8, 4, 16
    mamba = d * 2 * di + di * (dtr + 2 * n) + dtr * di + di * d
    attn = d * h * hd + 2 * d * hd + h * hd * d
    body = 13 * mamba + attn + 14 * 3 * d * ff
    assert fam.matmul_params(cfg, head=False) == body
    # one attention layer: 4 heads of 16, 10 causal pairs at s = 4
    assert fam.prefill_flops(cfg, 4) == (2.0 * body * 4 + 2 * 2 * h * hd * 10
                                         + 2.0 * 512 * d)
    assert fam.decode_flops(cfg, [5, 7]) == (
        2 * 2.0 * (body + 512 * d) + 2 * 2 * h * hd * 12)
    weights = 2 * (512 * d + d + 14 * (2 * d + 3 * d * ff)
                   + 13 * (mamba + 4 * di + di + dtr + 2 * n + di + di * n
                           + di) + attn)
    assert fam.weight_bytes(cfg) == weights
    # per slot: 13 Mamba layers' float32 SSM state and bf16 conv window,
    # read and written; keys and values of one layer, one kv head
    state = 13 * di * (n * 4 + 3 * 2)
    assert fam.decode_bytes(cfg, [5, 7]) == (weights + 2 * hd * 2 * 12
                                             + 2 * 2 * state)
    assert fam.scan_cost(cfg, 32) == (
        6.0 * 32 * di * n, float(32 * di * (2 + 4 + 2) + 2 * 32 * n * 4
                                 + n * di * 4))
    assert fam.scans_per_prefill(cfg) == 13


def test_cell_serves_and_passes_its_check(tree):
    res = run_cell(tree, "tinyjamba.ctx", seed=2 ** 31 + 19, seconds=2.0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert {"ttft_p95_ms", "itl_p95_ms", "requests_per_s",
            "setup_s"} <= set(res["metrics"])
    assert res["window"]["compiles"] == 0


def test_traced_run_leaves_out_device_readers(tree):
    """On the CPU there is no TPU plane: the scan's roofline and the other
    device-trace readers find nothing and are left out of the line."""
    res = run_cell(tree, "tinyjamba.ctx", seed=5, seconds=2.0, trace=True)
    assert res["correct"]
    assert "mamba_scan_roofline" not in res["metrics"]
    assert "decode_step_roofline" not in res["metrics"]


def test_control_fails_the_check(tree):
    res = run_cell(tree, "tinyjamba.ctx", seed=9, seconds=1.0, control=True)
    assert not res["correct"]
    gap = res["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert (res["compared"]["program_logit_gap"]["value"]
            <= gap["limit"])


def test_reference_agrees_with_the_program_reference():
    """The benchmark's reference and the repository's, on the same
    float32 weights, give the same logits."""
    from repro.models import reference_jamba as program_ref

    cfg = _tiny_config()
    w = fam.make_weights(cfg, 3)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    mc = fam.program_config(cfg)
    params = fam.to_program(w32, mc)
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    want = program_ref.forward(params, toks, mc)
    got = reference.logits(w32, cfg, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert float(np.std(np.asarray(want))) > 0.05
