"""The DeepSeek-V2 family on the CPU: a small cell of it added as files
runs end to end, its control fails the check, its costs match counts made
by hand, the benchmark's self-contained reference agrees with the
program's own (``repro.models.reference_deepseek_v2``), and its routing
replay reads each routing's margin."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtree
from benchtree import run_cell
from chipbench.families import deepseek_v2 as fam
from chipbench.reference import deepseek_v2 as reference

#: DeepSeek-V2's structure at small widths: 8 groups of 2 routed experts
#: (group 0 held), top 3 groups, 6 experts a token, 2 shared, YaRN
TINY_DS = dict(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, q_lora_rank=32, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_attention_heads=4, num_key_value_heads=4,
               num_hidden_layers=3, vocab_size=512, router_experts=16,
               n_routed_experts=2)


def _tiny_config():
    cfg = json.loads((benchtree.ROOT / "chipbench" / "configs"
                      / "deepseek-v2.json").read_text())
    cfg.update(TINY_DS)
    cfg["assumed"] = dict(cfg["assumed"], initializer_range=0.1)
    cfg["name"] = "tinyds"
    return cfg


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    t = benchtree.make_tree(tmp_path_factory.mktemp("bench"))
    b = t / "chipbench"
    (b / "configs" / "tinyds.json").write_text(json.dumps(_tiny_config()))
    mix = json.loads((b / "traffic" / "longdoc.json").read_text())
    mix["prompt"].update(median=24, min=8, round_up=[16, 32])
    mix["output"].update(min=2, max=8)
    mix["clients"] = 4
    (b / "traffic" / "tinydoc.json").write_text(json.dumps(mix))
    (b / "workloads" / "tinyds.doc.json").write_text(json.dumps(
        {"slots": 4, "max_len": 64, "check_tokens": 24,
         "limits": {"logit_gap": 0.05, "route_margin": 0.1}}))
    bench = json.loads((t / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tinyds", "source": "test",
                             "file": "chipbench/configs/tinyds.json",
                             "reduced": list(TINY_DS), "why": "test"})
    bench["workloads"].append({"name": "tinyds.doc", "config": "tinyds",
                               "traffic": "tinydoc", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "deepseek-v2.longdoc" in m.get("workloads", []):
            m["workloads"].append("tinyds.doc")
    (t / "BENCHMARK.json").write_text(json.dumps(bench))
    return t


def test_cell_serves_and_counts_held_expert_rows(tree):
    res = run_cell(tree, "tinyds.doc", seed=2 ** 31 + 11, seconds=2.0)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert {"itl_p95_ms", "requests_per_s", "setup_s"} <= set(res["metrics"])
    assert res["window"]["compiles"] == 0


def test_traced_run_records_launch_rows(tree):
    """On the CPU there is no TPU plane, so the two device-trace readers
    find nothing and are left out; the launches they read are recorded,
    and every served request's routing, one position after another."""
    seen = {}

    def keep(bench):
        seen["bench"] = bench

    res = run_cell(tree, "tinyds.doc", seed=5, seconds=2.0, trace=True,
                   prepare=keep)
    assert "moe_gmm_roofline" not in res["metrics"]
    assert "decode_step_roofline.longdoc" not in res["metrics"]
    launches = seen["bench"].moe_launches
    kinds = [k for per_call in launches.values() for k, _ in per_call]
    assert "prefill" in kinds and "generate" in kinds
    for per_call in launches.values():
        for _, rows in per_call:
            assert rows.shape == (2, 2)
    bench = seen["bench"]
    served = [r for r in bench.requests if r.result is not None]
    assert served
    for r in served:
        routes = np.concatenate(bench.routes[r.rid])
        assert routes.shape[1:] == (2, 6)
        assert len(routes) >= len(r.prompt) + len(r.result) - 1


def test_control_fails_the_check(tree):
    res = run_cell(tree, "tinyds.doc", seed=9, seconds=1.0, control=True)
    assert not res["correct"]
    gap = res["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    for name in ("logit_gap", "route_margin"):
        assert (res["compared"]["program_" + name]["value"]
                <= res["compared"][name]["limit"])


def test_costs_by_hand():
    cfg = _tiny_config()
    d, ff = 64, 32
    rows = np.array([[3, 0], [1, 2]])
    costs = fam.gmm_costs(cfg, rows)
    assert len(costs) == 6
    # layer 0: 3 rows, 1 expert touched; gate d->ff
    assert costs[0] == (2.0 * 3 * d * ff, (3 * (d + ff) + d * ff) * 2.0)
    # layer 1: 3 rows, 2 experts; down ff->d
    assert costs[5] == (2.0 * 3 * ff * d, (3 * (ff + d) + 2 * ff * d) * 2.0)
    # per layer: wq_a 64x32, wq_b 32x(4x24), wkv_a 64x24, wk_b 16x64,
    # wv_b 16x64, wo 64x64; dense MLP 3x64x128; per MoE layer router 64x16
    # and shared 3x64x64; head 64x512
    attn = 64 * 32 + 32 * 96 + 64 * 24 + 16 * 64 + 16 * 64 + 64 * 64
    tok = 3 * attn + 3 * 64 * 128 + 2 * (64 * 16 + 3 * 64 * 64) + 64 * 512
    assert fam.token_params(cfg) == tok
    assert fam.latent_bytes_per_token(cfg) == 3 * 24 * 2
    # absorbed attention: 4 heads x (2 x 16 + 8) x 2 flops x 3 layers
    att = 2 * 4 * (2 * 16 + 8) * 3
    assert fam.decode_flops(cfg, [5, 7], rows) == (
        2 * (2.0 * tok) + att * 12 + 2.0 * 6 * 3 * d * ff)
    assert fam.decode_bytes(cfg, [5, 7], rows) == (
        tok * 2 + 3 * 3 * d * ff * 2 + 3 * 24 * 2 * 12)


def test_prefill_costs_by_hand():
    cfg = _tiny_config()
    # 4 heads, keys at 16 + 8, values at 16; 10 causal pairs at s = 4
    flops, moved = fam.flash_cost(cfg, 4)
    assert flops == 2.0 * 4 * 10 * (24 + 16)
    assert moved == 4 * 4 * (2 * 24 + 2 * 16) * 2.0
    # a token sends 6 x 2 / 16 rows to the held experts of each MoE layer
    tok = fam.token_params(cfg) - 64 * 512
    assert fam.prefill_flops(cfg, 4) == (
        2.0 * tok * 4 + 2.0 * 2 * (4 * 6 * 2 / 16) * 3 * 64 * 32
        + 3 * flops + 2.0 * 64 * 512)


def test_reference_agrees_with_the_program_reference():
    """The benchmark's reference and the repository's, on the same
    weights: the same logits and the same routing; replaying that routing
    changes nothing and reads a margin of 0 everywhere."""
    from repro.models import reference_deepseek_v2 as program_ref

    cfg = _tiny_config()
    w = fam.make_weights(cfg, 3)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    mc = fam.program_config(dict(cfg, name="tinyds"))
    params = fam.to_program(w32, mc)
    toks = np.random.default_rng(0).integers(0, 512, 64).astype(np.int32)
    want, routed = program_ref.forward(params, toks, mc)
    x, own, margins = reference.hidden(w32, cfg, toks)
    got = reference.logits_at(w32, cfg, x, 0, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.sort(np.asarray(own), -1),
                                  np.sort(np.stack(routed), -1))
    assert float(np.max(margins)) == 0.0
    x2, _, margins = reference.hidden(w32, cfg, toks,
                                      routes=np.asarray(own)[:, :40])
    np.testing.assert_array_equal(np.asarray(x2), np.asarray(x))
    assert float(np.max(margins)) == 0.0


def _margin(logits, used):
    cfg = dict(_tiny_config(), router_experts=8, n_group=4, topk_group=2,
               num_experts_per_tok=2)
    return float(reference.route_margin(
        jnp.asarray([logits], jnp.float32), jnp.asarray([used]),
        reference.dims(cfg))[0])


@pytest.mark.parametrize("used,want", [
    ((0, 2), 0.0),        # the gate's own choice: experts 0 and 2
    ((2, 0), 0.0),        # in another order
    ((0, 3), 0.5),        # expert 3 (2.5) taken over 2 (3.0), groups kept
    ((0, 4), 2.0),        # group 2 (best 1.0) over group 1 (best 3.0)
    ((1, 2), 1.5),        # expert 1 (2.0) over 0 (3.5)
], ids=["own", "order", "expert", "group", "within-group"])
def test_route_margin_by_hand(used, want):
    """4 groups of 2 experts, 2 groups kept, 2 experts a token: the
    margin is how far the routing's worst pick lies below the best expert
    it left out, or its group below the last kept group."""
    logits = [3.5, 2.0, 3.0, 2.5, 1.0, 0.0, -1.0, 0.5]
    assert _margin(logits, used) == pytest.approx(want)
