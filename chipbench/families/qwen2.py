"""Qwen2-family decoders (qwen2.5-3b) served by ``DecodeEngine`` behind
``Server``.

A configuration file holds the model's published ``config.json`` keys; this
module turns them into the program's ``ModelConfig``, draws the weights
from the seed on the device, builds the engine and the server at the
cell's slots and ``max_len``, warms the prompt lengths of the cell's
traffic, drives the window, counts operations and bytes from shapes, and
checks what the window served against ``chipbench.reference.qwen2``.
"""

from __future__ import annotations

import gc
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..bench import traffic
from ..bench.lmserve import Calls, LMDriver, Served
from ..reference import qwen2 as reference

#: bytes of one bfloat16 element
BF16 = 2


def _dims(cfg: dict) -> dict:
    d, h, kvh = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd = d // h
    return dict(d=d, h=h, kvh=kvh, hd=hd, ff=cfg["intermediate_size"],
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                vocab_padded=-(-cfg["vocab_size"] // 256) * 256)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for these published keys."""
    from repro.models.config import ModelConfig

    if cfg.get("hidden_act") != "silu" or cfg.get("use_sliding_window"):
        raise ValueError("the qwen2 family here is SiLU with full attention")
    m = _dims(cfg)
    return ModelConfig(
        name=cfg["name"], n_layers=m["layers"], d_model=m["d"],
        n_heads=m["h"], n_kv_heads=m["kvh"], head_dim=m["hd"],
        d_ff=m["ff"], vocab=m["vocab"], qkv_bias=True,
        rope_theta=float(cfg["rope_theta"]), norm="rmsnorm",
        norm_eps=float(cfg["rms_norm_eps"]), act="silu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=str(cfg["torch_dtype"]))


def layout(cfg: dict) -> Dict[str, tuple]:
    """name -> (shape, law, scale) of the benchmark's flat weight layout.

    Matrices are N(0, initializer_range); norm gains are 1 + N(0, 0.1) and
    biases N(0, 0.1), so that the check sees every weight the model has."""
    m = _dims(cfg)
    L, d, ff = m["layers"], m["d"], m["ff"]
    q, kv = m["h"] * m["hd"], m["kvh"] * m["hd"]
    std = float(cfg["initializer_range"])
    out = {"embed": ((m["vocab_padded"], d), "normal", std),
           "ln_f": ((d,), "gain", 0.1)}
    for name, shape, law, scale in (
            ("ln1", (d,), "gain", 0.1), ("wq", (d, q), "normal", std),
            ("bq", (q,), "normal", 0.1), ("wk", (d, kv), "normal", std),
            ("bk", (kv,), "normal", 0.1), ("wv", (d, kv), "normal", std),
            ("bv", (kv,), "normal", 0.1), ("wo", (q, d), "normal", std),
            ("ln2", (d,), "gain", 0.1), ("gate", (d, ff), "normal", std),
            ("up", (d, ff), "normal", std), ("down", (ff, d), "normal", std)):
        out["layers/" + name] = ((L,) + shape, law, scale)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight drawn from ``seed`` on the device, in bfloat16, by one
    jitted call."""
    lay = layout(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draw(key):
        flat = {}
        for i, (name, (shape, law, scale)) in enumerate(sorted(lay.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            x = x * jnp.asarray(scale, dtype)
            flat[name] = x + jnp.asarray(1, dtype) if law == "gain" else x
        return flat

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    flat = jax.jit(draw)(key)
    w = {"layers": {}}
    for name, x in flat.items():
        if name.startswith("layers/"):
            w["layers"][name[len("layers/"):]] = x
        else:
            w[name] = x
    return w


def to_program(weights: dict, mc) -> dict:
    """The same arrays, arranged as the program's parameter tree."""
    from repro.models import model_spec
    from repro.models.params import abstract_params

    lw = weights["layers"]
    tree = {"embed": {"embedding": weights["embed"]},
            "final_norm": {"scale": weights["ln_f"]},
            "blocks": {"pos0": {
                "norm1": {"scale": lw["ln1"]},
                "block": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                          "wo": lw["wo"], "bq": lw["bq"], "bk": lw["bk"],
                          "bv": lw["bv"]},
                "norm2": {"scale": lw["ln2"]},
                "mlp": {"wg": lw["gate"], "wi": lw["up"],
                        "wo": lw["down"]}}}}
    want = abstract_params(model_spec(mc))
    got_shapes = jax.tree_util.tree_map(lambda x: x.shape, tree)
    want_shapes = jax.tree_util.tree_map(lambda x: x.shape, want)
    if got_shapes != want_shapes:
        raise ValueError(f"the program's parameter tree changed: "
                         f"{want_shapes} != {got_shapes}")
    return tree


# -- operations and bytes, from shapes ---------------------------------------
def matmul_params(cfg: dict, head: bool = True) -> int:
    """Weights one token multiplies through (the logits head if ``head``)."""
    m = _dims(cfg)
    per_layer = (m["d"] * m["h"] * m["hd"] * 2 + m["d"] * m["kvh"] * m["hd"]
                 * 2 + 3 * m["d"] * m["ff"])
    return m["layers"] * per_layer + (m["vocab"] * m["d"] if head else 0)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight, as served (the padded vocabulary included)."""
    return sum(int(np.prod(s)) for s, _, _ in layout(cfg).values()) * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    m = _dims(cfg)
    return m["layers"] * 2 * m["kvh"] * m["hd"] * BF16


def attention_flops(cfg: dict, q_len: int, kv_len: int,
                    causal: bool) -> float:
    """Score and value products of every layer's heads: 2 flops per
    multiply-add, causal counting only the pairs at or below the diagonal."""
    m = _dims(cfg)
    pairs = (q_len * (q_len + 1) / 2 if causal else q_len * kv_len)
    return m["layers"] * 2 * 2 * m["h"] * m["hd"] * pairs


def decode_flops(cfg: dict, live: List[int]) -> float:
    """One decode step's model flops for the live slots (kv lengths)."""
    m = _dims(cfg)
    return sum(2.0 * matmul_params(cfg)
               + m["layers"] * 2 * 2 * m["h"] * m["hd"] * n for n in live)


def decode_bytes(cfg: dict, live: List[int]) -> float:
    """Bytes one decode step must move: every weight once, the ``n - 1``
    cached keys and values of each live slot (kv length ``n``) read, and
    its new key and value written."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(live)


def prefill_flops(cfg: dict, s: int) -> float:
    """A batch-1 prefill of ``s`` tokens, with the logits of its last."""
    m = _dims(cfg)
    return (2.0 * matmul_params(cfg, head=False) * s
            + attention_flops(cfg, s, s, causal=True)
            + 2.0 * m["vocab"] * m["d"])


def flash_cost(cfg: dict, s: int) -> tuple:
    """(flops, bytes) of ONE layer's causal flash-attention call at ``s``."""
    m = _dims(cfg)
    flops = attention_flops(cfg, s, s, causal=True) / m["layers"]
    moved = (2 * s * m["h"] * m["hd"] + 2 * s * m["kvh"] * m["hd"]) * BF16
    return flops, moved


class Bench:
    """One cell of this family: set-up, window, release, check."""

    def __init__(self, cell, seed: int, calls: Calls):
        self.cell, self.seed, self.calls = cell, seed, calls
        self.cfg = cell.config
        self.serve = cell.serve
        self.mix = cell.traffic
        self.requests: List[Served] = []

    # -- set-up ---------------------------------------------------------------
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

    # -- the window -----------------------------------------------------------
    def window(self, t0: float, seconds: float, on_tick) -> None:
        vocab = self.cfg["vocab_size"]
        self.driver.on_tick = on_tick
        if self.mix["loop"] == "open":
            plan = traffic.open_schedule(self.mix, self.seed, seconds,
                                         self.serve["rate"])
            self.requests = [
                Served(index=r.index, max_new=r.max_new, due=t0 + r.due,
                       prompt=traffic.prompt_tokens(self.seed, r.index,
                                                    r.prompt_len, vocab))
                for r in plan]
            self.driver.open_loop(self.requests)
        else:
            stream = traffic.closed_stream(self.mix, self.seed)
            end = t0 + seconds

            def next_request(now):
                if now >= end:
                    return None
                r = next(stream)
                req = Served(index=r.index, max_new=r.max_new, due=now,
                             prompt=traffic.prompt_tokens(
                                 self.seed, r.index, r.prompt_len, vocab))
                self.requests.append(req)
                return req

            self.driver.closed_loop(next_request, self.mix["clients"])

    def completions(self) -> List[float]:
        return [r.times[-1] for r in self.requests if r.result is not None]

    def launches(self) -> int:
        return self.engine.worker.n_batches

    def attempted(self) -> int:
        return len(self.requests)

    def failed(self) -> int:
        return sum(r.result is None for r in self.requests)

    def lateness_p95(self) -> Optional[float]:
        """How late the open loop sent its requests (95th percentile, s)."""
        return traffic.quantile([r.t_submit - r.due for r in self.requests
                                 if r.t_submit], 0.95)

    def release(self) -> None:
        del self.server, self.engine, self.driver
        gc.collect()

    # -- correctness ------------------------------------------------------------
    def sample(self) -> List[Served]:
        """The requests the check replays: the longest, then others drawn
        from the seed until ``check_tokens`` served tokens are covered."""
        done = [r for r in self.requests if r.result is not None]
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r.prompt) + len(r.result),
                                           -r.index))
        rng = np.random.default_rng([self.seed, 7])
        out, n = [longest], len(longest.result)
        for i in rng.permutation(len(done)):
            if n >= self.serve["check_tokens"]:
                break
            if done[i] is not longest:
                out.append(done[i])
                n += len(done[i].result)
        return out

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
        limits = self.serve["limits"]
        out = {"unserved": (float(unserved), 0.0),
               "stream_vs_result": (float(mismatch), 0.0),
               "logit_gap": (max(gaps) if gaps else float("inf"),
                             limits["logit_gap"])}
        if control:
            out["control_logit_gap"] = (max(ctl) if ctl else float("inf"),
                                        limits["logit_gap"])
        return out
