"""DeepSeek-V2 as one chip of an expert-parallel deployment, served by
``DecodeEngine`` behind ``Server``.

The configuration file holds the published ``config.json`` keys, cut as
its ``deployment`` says: ``n_routed_experts`` counts the experts this chip
holds (from ``first_held_expert``) of the router's ``router_experts``,
``vocab_size`` the vocabulary rows held here, ``num_hidden_layers`` the
layers kept.  This module turns those keys into the program's
``ModelConfig``, draws the weights from the seed on the device, serves the
cell's traffic through the same set-up, window and check as the Qwen2
family, records each engine launch's routed experts (per launch: its rows
per MoE layer and held expert; per request: every position's experts),
counts operations and bytes from shapes and those rows, and checks what
the window served against ``chipbench.reference.deepseek_v2``, replaying
each request's routing there and judging the routing by its margin.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..bench import traffic
from ..bench.lmserve import Calls, LMDriver
from ..reference import deepseek_v2 as reference
from . import qwen2

BF16 = 2
ATTN = ("ln1", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b",
        "wo", "ln2")


def _dims(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return dict(
        d=cfg["hidden_size"], h=h, ql=cfg["q_lora_rank"],
        kvl=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
        dense_ff=cfg["intermediate_size"], ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        held=cfg["n_routed_experts"], router=cfg["router_experts"],
        k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
        vocab_padded=-(-cfg["vocab_size"] // 256) * 256,
        n_moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
        layers=cfg["num_hidden_layers"])


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for these keys."""
    from repro.models.config import ModelConfig

    if (cfg.get("hidden_act") != "silu" or cfg.get("scoring_func") != "softmax"
            or cfg.get("topk_method") != "group_limited_greedy"
            or cfg["rope_scaling"].get("type") != "yarn"
            or cfg["first_k_dense_replace"] != 1
            or cfg["moe_layer_freq"] != 1 or cfg["attention_bias"]
            or cfg["tie_word_embeddings"]):
        raise ValueError("the deepseek_v2 family here is SiLU, softmax "
                         "group-limited routing, YaRN, one dense layer, no "
                         "attention bias, an untied head")
    m = _dims(cfg)
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], n_layers=m["layers"], d_model=m["d"],
        n_heads=m["h"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=m["nope"], d_ff=m["ff"], vocab=m["vocab"],
        block_pattern=("mla",), mlp_pattern=("moe",), first_layer_dense=True,
        d_ff_dense=m["dense_ff"], attn_kind="mla", q_lora_rank=m["ql"],
        kv_lora_rank=m["kvl"], qk_nope_head_dim=m["nope"],
        qk_rope_head_dim=m["rope"], v_head_dim=m["vd"],
        n_experts=m["router"], n_shared_experts=cfg["n_shared_experts"],
        top_k=m["k"], d_ff_expert=m["ff"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        expert_first=cfg["first_held_expert"], experts_held=m["held"],
        rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original_max_position=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]), act="silu",
        tie_embeddings=False, dtype=str(cfg["torch_dtype"]))


def _attention_layout(m: dict) -> Dict[str, tuple]:
    q = m["h"] * (m["nope"] + m["rope"])
    return {"ln1": (m["d"],), "wq_a": (m["d"], m["ql"]),
            "q_norm": (m["ql"],), "wq_b": (m["ql"], q),
            "wkv_a": (m["d"], m["kvl"] + m["rope"]),
            "kv_norm": (m["kvl"],), "wk_b": (m["kvl"], m["h"] * m["nope"]),
            "wv_b": (m["kvl"], m["h"] * m["vd"]),
            "wo": (m["h"] * m["vd"], m["d"]), "ln2": (m["d"],)}


def layout(cfg: dict) -> Dict[str, tuple]:
    """name -> (shape, law) of the benchmark's flat weight layout: matrices
    N(0, initializer_range) (assumed: the catalog gives none), norm gains
    1 + N(0, 0.1)."""
    m = _dims(cfg)
    d, ff, sff, held = m["d"], m["ff"], m["shared_ff"], m["held"]
    out = {"embed": ((m["vocab_padded"], d), "normal"),
           "head": ((d, m["vocab_padded"]), "normal"),
           "ln_f": ((d,), "gain")}
    attn = _attention_layout(m)
    dense = dict(attn, gate=(d, m["dense_ff"]), up=(d, m["dense_ff"]),
                 down=(m["dense_ff"], d))
    moe = dict(attn, router=(d, m["router"]), gate_e=(held, d, ff),
               up_e=(held, d, ff), down_e=(held, ff, d), gate_s=(d, sff),
               up_s=(d, sff), down_s=(sff, d))
    for name, shape in dense.items():
        out["dense/" + name] = (shape, "gain" if len(shape) == 1 else "normal")
    for name, shape in moe.items():
        out["moe/" + name] = ((m["n_moe"],) + shape,
                              "gain" if len(shape) == 1 else "normal")
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight drawn from ``seed`` on the device, in bfloat16, by one
    jitted call."""
    lay = layout(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg["assumed"]["initializer_range"])

    def draw(key):
        flat = {}
        for i, (name, (shape, law)) in enumerate(sorted(lay.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            if law == "gain":
                flat[name] = x * jnp.asarray(0.1, dtype) + jnp.asarray(1, dtype)
            else:
                flat[name] = x * jnp.asarray(std, dtype)
        return flat

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    flat = jax.jit(draw)(key)
    w: dict = {"dense": {}, "moe": {}}
    for name, x in flat.items():
        group, _, leaf = name.rpartition("/")
        (w[group] if group else w)[leaf] = x
    return w


def to_program(weights: dict, mc) -> dict:
    """The same arrays, arranged as the program's parameter tree."""
    from repro.models import model_spec
    from repro.models.params import abstract_params

    def block(w):
        return {k: w[k] for k in ATTN if k not in ("ln1", "ln2")}

    dw, mw = weights["dense"], weights["moe"]
    tree = {
        "embed": {"embedding": weights["embed"], "lm_head": weights["head"]},
        "final_norm": {"scale": weights["ln_f"]},
        "layer0": {"norm1": {"scale": dw["ln1"]}, "block": block(dw),
                   "norm2": {"scale": dw["ln2"]},
                   "mlp": {"wg": dw["gate"], "wi": dw["up"],
                           "wo": dw["down"]}},
        "blocks": {"pos0": {
            "norm1": {"scale": mw["ln1"]}, "block": block(mw),
            "norm2": {"scale": mw["ln2"]},
            "mlp": {"router": mw["router"], "wg": mw["gate_e"],
                    "wi": mw["up_e"], "wo": mw["down_e"],
                    "shared": {"wg": mw["gate_s"], "wi": mw["up_s"],
                               "wo": mw["down_s"]}}}}}
    want = abstract_params(model_spec(mc))
    got_shapes = jax.tree_util.tree_map(lambda x: x.shape, tree)
    want_shapes = jax.tree_util.tree_map(lambda x: x.shape, want)
    if got_shapes != want_shapes:
        raise ValueError(f"the program's parameter tree changed: "
                         f"{want_shapes} != {got_shapes}")
    return tree


# -- operations and bytes, from shapes and routed rows -----------------------
def expert_params(cfg: dict) -> int:
    """Weights of one routed expert (gate, up, down)."""
    m = _dims(cfg)
    return 3 * m["d"] * m["ff"]


def token_params(cfg: dict) -> int:
    """Weights one token multiplies through besides the routed experts:
    every layer's MLA projections (``wk_b`` and ``wv_b`` as the absorbed
    decode uses them), the dense MLP, each MoE layer's router and shared
    experts, and the head."""
    m = _dims(cfg)
    attn = sum(int(np.prod(s)) for n, s in _attention_layout(m).items()
               if len(s) == 2)
    moe = m["d"] * m["router"] + 3 * m["d"] * m["shared_ff"]
    return (m["layers"] * attn + 3 * m["d"] * m["dense_ff"]
            + m["n_moe"] * moe + m["d"] * m["vocab"])


def latent_bytes_per_token(cfg: dict) -> int:
    m = _dims(cfg)
    return m["layers"] * (m["kvl"] + m["rope"]) * BF16


def gmm_costs(cfg: dict, rows: np.ndarray) -> List[Tuple[float, float]]:
    """(flops, bytes) of each grouped matmul of one launch whose routed
    rows per MoE layer and held expert are ``rows`` (L, held): gate, up
    and down of every layer, counting the routed rows in and out and each
    touched expert's weight once."""
    m = _dims(cfg)
    out = []
    for layer in np.asarray(rows):
        r, t = int(layer.sum()), int((layer > 0).sum())
        for k, n in ((m["d"], m["ff"]), (m["d"], m["ff"]), (m["ff"], m["d"])):
            out.append((2.0 * r * k * n, float((r * (k + n) + t * k * n)
                                               * BF16)))
    return out


def decode_flops(cfg: dict, live: List[int], rows: np.ndarray) -> float:
    """One decode step's model flops: every live slot (kv length ``n``)
    through the non-expert weights and absorbed attention over its ``n``
    latents (scores over kv_lora + rope, values over kv_lora), and the
    routed rows through their experts."""
    m = _dims(cfg)
    att = 2 * m["h"] * (2 * m["kvl"] + m["rope"]) * m["layers"]
    return (sum(2.0 * token_params(cfg) + att * n for n in live)
            + 2.0 * float(np.sum(rows)) * expert_params(cfg))


def decode_bytes(cfg: dict, live: List[int], rows: np.ndarray) -> float:
    """Bytes one decode step must move: every non-expert weight once (the
    embedding table only for the rows looked up), the weights of each
    touched (layer, expert) once, the ``n`` latents of each live slot."""
    touched = int((np.asarray(rows) > 0).sum())
    return (token_params(cfg) * BF16 + touched * expert_params(cfg) * BF16
            + latent_bytes_per_token(cfg) * sum(live))


def flash_cost(cfg: dict, s: int) -> tuple:
    """(flops, bytes) of ONE layer's causal flash-attention call at ``s``:
    MLA's expanded prefill, every head's keys at ``qk_nope_head_dim +
    qk_rope_head_dim`` and values at ``v_head_dim``; causal pairs only."""
    m = _dims(cfg)
    dk, dv = m["nope"] + m["rope"], m["vd"]
    flops = 2.0 * m["h"] * (s * (s + 1) / 2) * (dk + dv)
    moved = s * m["h"] * (2 * dk + 2 * dv) * BF16
    return flops, float(moved)


def prefill_flops(cfg: dict, s: int) -> float:
    """A batch-1 prefill of ``s`` tokens with the logits of its last:
    every token through the non-expert weights, each MoE layer's held
    experts at the rows a token sends this chip on average (``top_k``
    times the held share of the router's experts), and every layer's
    causal attention."""
    m = _dims(cfg)
    body = token_params(cfg) - m["d"] * m["vocab"]
    rows = s * m["k"] * m["held"] / m["router"]
    return (2.0 * body * s + 2.0 * m["n_moe"] * rows * expert_params(cfg)
            + m["layers"] * flash_cost(cfg, s)[0] + 2.0 * m["d"] * m["vocab"])


class Bench(qwen2.Bench):
    """One cell of this family: the Qwen2 family's window over this
    family's model, weights, launch records and check."""

    def __init__(self, cell, seed: int, calls: Calls):
        super().__init__(cell, seed, calls)
        #: call id -> [(launch kind, routed rows (L, held))] of the window
        self.moe_launches: Dict[int, List[tuple]] = {}
        #: request id -> its positions' routed experts, (tokens, L, k)
        #: blocks in position order: the prompt's, then one a decode step
        self.routes: Dict[int, List[np.ndarray]] = {}

    def setup(self) -> None:
        from repro.serve import DecodeEngine, Server

        self.mc = program_config(self.cfg)
        self.weights = make_weights(self.cfg, self.seed)
        params = to_program(self.weights, self.mc)
        self.engine = DecodeEngine(self.mc, params,
                                   num_slots=self.serve["slots"],
                                   max_len=self.serve["max_len"])
        self.server = Server((), workers=(), engine=self.engine)
        for s in traffic.prompt_lengths(self.mix):
            rid = self.server.submit_decode(np.zeros(s, np.int32), 2)
            self.server.flush()
            self.server.result(rid)
        self.driver = LMDriver(self.server, self.engine, self.calls)
        self._record_launches()

    def _record_launches(self) -> None:
        """Note each engine launch's routed rows under the call it ran in
        (the last call opened: the engine launches inside the driver's),
        and its routed experts under the requests it ran."""
        engine = self.engine
        prefill, generate = engine.prefill, engine.generate

        def launched(kind):
            if self.calls.calls:
                self.moe_launches.setdefault(
                    self.calls.calls[-1].id, []).append(
                        (kind, engine.moe_last_rows))
            return engine.moe_last_experts.astype(np.int16)

        def recorded_prefill(*args, **kw):
            prefix = prefill(*args, **kw)
            self.routes[prefix.rid] = [launched("prefill")]
            return prefix

        def recorded_generate(params, state):
            live = [(slot, rid) for slot, (held, rid)
                    in enumerate(zip(state.occupied, state.rids)) if held]
            out = generate(params, state)
            experts = launched("generate")
            for slot, rid in live:
                self.routes.setdefault(rid, []).append(
                    experts[slot: slot + 1])
            return out

        engine.prefill, engine.generate = recorded_prefill, recorded_generate

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit."""
        unserved = sum(r.result is None for r in self.requests)
        mismatch = sum(r.result is not None
                       and list(r.result) != r.tokens[: len(r.result)]
                       for r in self.requests)
        readings, ctl = [], []
        for r in self.sample():
            routes = np.concatenate(self.routes[r.rid]).transpose(1, 0, 2)
            got, c = reference.served_gap(
                self.weights, self.cfg, r.prompt, np.asarray(r.result),
                routes, self.serve["max_len"], control)
            readings.append(got)
            ctl.append(c)
        limits = self.serve["limits"]
        out = {"unserved": (float(unserved), 0.0),
               "stream_vs_result": (float(mismatch), 0.0)}
        for i, name in enumerate(("logit_gap", "route_margin")):
            out[name] = (max((g[i] for g in readings), default=float("inf")),
                         limits[name])
            if control:
                out["control_" + name] = (
                    max((c[i] for c in ctl), default=float("inf")),
                    limits[name])
        return out
