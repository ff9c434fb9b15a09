"""Jamba hybrids (AI21-Jamba2-3B) served by ``DecodeEngine`` behind
``Server``: Mamba-1 layers beside position-free grouped-query attention.

The configuration file holds the published ``config.json`` keys.  This
module turns them into the program's ``ModelConfig`` (a period of
``attn_layer_period`` layers, attention at ``attn_layer_offset``, Mamba-1
with its dt/B/C norms elsewhere, no rope, a dense MLP everywhere), draws
the weights from the seed on the device leaf by leaf, serves the cell's
traffic through the Qwen2 family's set-up and window, counts operations
and bytes from shapes, and checks what the window served against
``chipbench.reference.jamba``.

The benchmark's weight layout is the program's: one group of leaves per
position of the period, each stacked over the ``num_hidden_layers /
attn_layer_period`` groups, so that handing them to the program copies
nothing.  Layer ``i`` is position ``i % attn_layer_period`` of group
``i // attn_layer_period``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..bench import traffic
from ..bench.lmserve import LMDriver
from ..reference import jamba as reference
from . import qwen2

BF16, F32 = 2, 4
MAMBA = ("ln1", "in_proj", "conv_w", "conv_b", "x_proj", "dt_norm",
         "b_norm", "c_norm", "dt_proj", "dt_bias", "a_log", "d", "out_proj")
ATTN = ("ln1", "wq", "wk", "wv", "wo")
MLP = ("ln2", "gate", "up", "down")


def _dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    period = cfg["attn_layer_period"]
    return dict(
        d=d, h=h, kvh=cfg["num_key_value_heads"], hd=d // h,
        ff=cfg["intermediate_size"], di=cfg["mamba_expand"] * d,
        n=cfg["mamba_d_state"], dc=cfg["mamba_d_conv"],
        dtr=cfg["mamba_dt_rank"], period=period,
        offset=cfg["attn_layer_offset"], groups=cfg["num_hidden_layers"]
        // period, layers=cfg["num_hidden_layers"],
        n_attn=cfg["num_hidden_layers"] // period,
        n_mamba=cfg["num_hidden_layers"] // period * (period - 1),
        vocab=cfg["vocab_size"],
        vocab_padded=-(-cfg["vocab_size"] // 256) * 256)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for these published keys."""
    from repro.models.config import ModelConfig

    if (cfg.get("model_type") != "jamba" or cfg.get("hidden_act") != "silu"
            or cfg["num_experts"] != 1 or not cfg["mamba_conv_bias"]
            or cfg["mamba_proj_bias"] or cfg.get("sliding_window")
            or cfg["num_hidden_layers"] % cfg["attn_layer_period"]):
        raise ValueError("the jamba family here is SiLU, dense MLPs, a conv "
                         "bias, no projection bias, full attention, whole "
                         "periods of layers")
    m = _dims(cfg)
    kinds = tuple("attn" if p == m["offset"] else "mamba"
                  for p in range(m["period"]))
    return ModelConfig(
        name=cfg["name"], n_layers=m["layers"], d_model=m["d"],
        n_heads=m["h"], n_kv_heads=m["kvh"], head_dim=m["hd"],
        d_ff=m["ff"], vocab=m["vocab"], block_pattern=kinds,
        mlp_pattern=("dense",) * m["period"], rotary_pct=0.0,
        mamba_d_state=m["n"], mamba_d_conv=m["dc"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=m["dtr"],
        norm="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]), act="silu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


def _mixer_shapes(m: dict, kind: str) -> Dict[str, tuple]:
    d, di, n, dtr = m["d"], m["di"], m["n"], m["dtr"]
    if kind == "attn":
        q, kv = m["h"] * m["hd"], m["kvh"] * m["hd"]
        return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "wo": (q, d)}
    return {"ln1": (d,), "in_proj": (d, 2 * di), "conv_w": (m["dc"], di),
            "conv_b": (di,), "x_proj": (di, dtr + 2 * n), "dt_norm": (dtr,),
            "b_norm": (n,), "c_norm": (n,), "dt_proj": (dtr, di),
            "dt_bias": (di,), "a_log": (di, n), "d": (di,),
            "out_proj": (di, d)}


#: how each leaf is drawn (matrices and the embedding: "normal")
LAWS = {"ln1": "gain", "ln2": "gain", "ln_f": "gain", "dt_norm": "gain",
        "b_norm": "gain", "c_norm": "gain", "conv_b": "normal",
        "dt_bias": "dt_bias", "a_log": "a_log", "d": "ones"}


def layout(cfg: dict) -> Dict[str, tuple]:
    """name -> (shape, law) of the benchmark's weight layout: ``embed``,
    ``ln_f``, and ``pos{p}/<leaf>`` stacked over the groups."""
    m = _dims(cfg)
    out = {"embed": ((m["vocab_padded"], m["d"]), "normal"),
           "ln_f": ((m["d"],), "gain")}
    mlp = {"ln2": (m["d"],), "gate": (m["d"], m["ff"]),
           "up": (m["d"], m["ff"]), "down": (m["ff"], m["d"])}
    for p in range(m["period"]):
        kind = "attn" if p == m["offset"] else "mamba"
        for name, shape in dict(_mixer_shapes(m, kind), **mlp).items():
            out[f"pos{p}/{name}"] = ((m["groups"],) + shape,
                                     LAWS.get(name, "normal"))
    return out


@functools.partial(jax.jit, static_argnames=("shape", "law", "std", "dtype"))
def _draw(key, *, shape, law, std, dtype):
    if law == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)
    if law == "ones":
        return jnp.ones(shape, dtype)
    if law == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    x = jax.random.normal(key, shape, dtype)
    if law == "gain":
        return x * jnp.asarray(0.1, dtype) + jnp.asarray(1, dtype)
    return x * jnp.asarray(std, dtype)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight drawn from ``seed`` on the device, in the configured
    precision, one jitted draw a leaf: matrices, the embedding and the
    conv bias N(0, initializer_range); norm gains 1 + N(0, 0.1); Mamba's
    own init of ``A_log`` (log 1..d_state), ``D`` (1) and the dt bias
    (the inverse softplus of a dt log-uniform in [1e-3, 1e-1])."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg["assumed"]["initializer_range"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    w: dict = {}
    for i, (name, (shape, law)) in enumerate(sorted(layout(cfg).items())):
        group, _, leaf = name.rpartition("/")
        x = _draw(jax.random.fold_in(key, i), shape=shape, law=law, std=std,
                  dtype=dtype)
        (w.setdefault(group, {}) if group else w)[leaf] = x
    return w


def to_program(weights: dict, mc) -> dict:
    """The same arrays, arranged as the program's parameter tree."""
    from repro.models import model_spec
    from repro.models.params import abstract_params

    rename = {"d": "d_skip", "ln1": None, "ln2": None}
    blocks = {}
    for p, kind in enumerate(mc.block_pattern):
        pw = weights[f"pos{p}"]
        mixer = MAMBA if kind == "mamba" else ATTN
        blocks[f"pos{p}"] = {
            "norm1": {"scale": pw["ln1"]},
            "block": {rename.get(k, k): pw[k] for k in mixer
                      if rename.get(k, k)},
            "norm2": {"scale": pw["ln2"]},
            "mlp": {"wg": pw["gate"], "wi": pw["up"], "wo": pw["down"]}}
    tree = {"embed": {"embedding": weights["embed"]},
            "final_norm": {"scale": weights["ln_f"]}, "blocks": blocks}
    want = abstract_params(model_spec(mc))
    got_shapes = jax.tree_util.tree_map(lambda x: x.shape, tree)
    want_shapes = jax.tree_util.tree_map(lambda x: x.shape, want)
    if got_shapes != want_shapes:
        raise ValueError(f"the program's parameter tree changed: "
                         f"{want_shapes} != {got_shapes}")
    return tree


# -- operations and bytes, from shapes ---------------------------------------
def matmul_params(cfg: dict, head: bool = True) -> int:
    """Weights one token multiplies through (the tied head if ``head``):
    each Mamba layer's in_proj, x_proj, dt_proj and out_proj, each
    attention layer's projections, every layer's MLP."""
    m = _dims(cfg)
    mamba = sum(math.prod(s) for k, s in _mixer_shapes(m, "mamba").items()
                if k in ("in_proj", "x_proj", "dt_proj", "out_proj"))
    attn = sum(math.prod(s) for k, s in _mixer_shapes(m, "attn").items()
               if k != "ln1")
    return (m["n_mamba"] * mamba + m["n_attn"] * attn
            + m["layers"] * 3 * m["d"] * m["ff"]
            + (m["vocab"] * m["d"] if head else 0))


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight, as served (the padded vocabulary included)."""
    return sum(math.prod(s) for s, _ in layout(cfg).values()) * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position in every attention layer."""
    m = _dims(cfg)
    return m["n_attn"] * 2 * m["kvh"] * m["hd"] * BF16


def recurrent_bytes(cfg: dict) -> int:
    """One slot's Mamba state in every Mamba layer: the float32 SSM state
    (d_state x d_inner) and the conv window (d_conv - 1 rows of d_inner)."""
    m = _dims(cfg)
    return m["n_mamba"] * m["di"] * (m["n"] * F32 + (m["dc"] - 1) * BF16)


def attention_flops(cfg: dict, s: int) -> float:
    """Causal score and value products of a prompt of ``s`` in every
    attention layer, 2 flops a multiply-add."""
    m = _dims(cfg)
    return m["n_attn"] * 2 * 2 * m["h"] * m["hd"] * s * (s + 1) / 2


def prefill_flops(cfg: dict, s: int) -> float:
    """A batch-1 prefill of ``s`` tokens, with the logits of its last:
    every matmul, the attention layers' causal attention (the scan and
    the conv, vector work, are left out)."""
    m = _dims(cfg)
    return (2.0 * matmul_params(cfg, head=False) * s
            + attention_flops(cfg, s) + 2.0 * m["vocab"] * m["d"])


def decode_flops(cfg: dict, live: List[int]) -> float:
    """One decode step's model flops for the live slots (kv lengths)."""
    m = _dims(cfg)
    return sum(2.0 * matmul_params(cfg)
               + m["n_attn"] * 2 * 2 * m["h"] * m["hd"] * n for n in live)


def decode_bytes(cfg: dict, live: List[int]) -> float:
    """Bytes one decode step must move: every weight once, the cached
    keys and values of each live slot (kv length ``n``) in the attention
    layers, and each live slot's Mamba state read and written."""
    return (weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(live)
            + 2 * recurrent_bytes(cfg) * len(live))


def scan_cost(cfg: dict, s: int) -> tuple:
    """(flops, bytes) of ONE layer's selective scan over a prompt of
    ``s``: per step, channel and state entry the decay's product and
    exponent, the increment, the update and the readout (6 flops);
    x (bfloat16), delta, B and C (float32) read, y (bfloat16) and the
    float32 final state written."""
    m = _dims(cfg)
    flops = 6.0 * s * m["di"] * m["n"]
    moved = (s * m["di"] * (BF16 + F32 + BF16) + 2 * s * m["n"] * F32
             + m["n"] * m["di"] * F32)
    return flops, float(moved)


def scans_per_prefill(cfg: dict) -> int:
    """Selective scans one prefill runs: one a Mamba layer."""
    return _dims(cfg)["n_mamba"]


class Bench(qwen2.Bench):
    """One cell of this family: the Qwen2 family's window over this
    family's model, weights and check."""

    def setup(self) -> None:
        from repro.serve import DecodeEngine, Server

        # every prompt is exactly one of the mix's lengths, each warmed
        # here: a recurrent prefill must see no padding token, and the
        # window compiles nothing
        lengths = traffic.prompt_lengths(self.mix)
        self.mc = program_config(self.cfg)
        self.weights = make_weights(self.cfg, self.seed)
        params = to_program(self.weights, self.mc)
        self.engine = DecodeEngine(self.mc, params,
                                   num_slots=self.serve["slots"],
                                   max_len=self.serve["max_len"])
        self.server = Server((), workers=(), engine=self.engine)
        for s in lengths:
            rid = self.server.submit_decode(np.zeros(s, np.int32), 2)
            self.server.flush()
            self.server.result(rid)
        self.driver = LMDriver(self.server, self.engine, self.calls)

    def check(self, control: bool = False) -> dict:
        """The numbers compared, each with its limit."""
        unserved = sum(r.result is None for r in self.requests)
        mismatch = sum(r.result is not None
                       and list(r.result) != r.tokens[: len(r.result)]
                       for r in self.requests)
        gaps, ctl = [], []
        for r in self.sample():
            g, c = reference.served_gap(self.weights, self.cfg, r.prompt,
                                        np.asarray(r.result),
                                        self.serve["max_len"], control)
            gaps.append(g)
            ctl.append(c)
        limit = self.serve["limits"]["logit_gap"]
        out = {"unserved": (float(unserved), 0.0),
               "stream_vs_result": (float(mismatch), 0.0),
               "logit_gap": (max(gaps) if gaps else float("inf"), limit)}
        if control:
            out["control_logit_gap"] = (max(ctl) if ctl else float("inf"),
                                        limit)
        return out
