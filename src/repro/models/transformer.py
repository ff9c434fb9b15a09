"""The model stack: embed → lax.scan over layer groups → norm → logits.

One periodic *group* holds ``cfg.block_pattern`` block positions (e.g. jamba:
1 attn + 7 mamba).  Parameters for each position are stacked over
``n_groups`` and the stack is a single ``lax.scan``, so HLO size is
O(period), not O(depth) — mistral-large's 88 layers lower as one scan of 22
groups (essential for the 1-CPU multi-pod dry-run, and what a real TPU build
wants anyway).

Three entry points (all pure, jit/pjit-able):

* :func:`forward`      — full-sequence hidden states (train / encoder);
* :func:`train_loss`   — CE loss + MoE aux losses + metrics;
* :func:`prefill` / :func:`decode_step` — serving with per-kind caches
  (KV / MLA-latent / mamba-state / rwkv-state); prefill emits them as scan
  ys, decode carries the stacks through its scan and writes in place.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from .attention import (attend_decode, attend_full, attn_spec,
                        cache_from_prefill, init_kv_cache, kv_cache_struct)
from .config import ModelConfig
from .frontends import embed_audio, embed_vision, frontend_spec
from .layers import (apply_mlp, apply_norm, cdtype, cross_entropy,
                     embed_spec, embed_tokens, logits_from_hidden, mlp_spec,
                     norm_spec, residual_scale)
from .mamba import (init_mamba_state, mamba_decode, mamba_full, mamba_spec,
                    mamba_state_struct)
from .mla import (init_mla_cache, mla_cache_from_prefill, mla_cache_struct,
                  mla_decode, mla_full, mla_spec)
from .moe import apply_moe, apply_moe_serve, moe_spec
from .rwkv import (init_rwkv_state, rwkv_channel_mix, rwkv_spec,
                   rwkv_state_struct, rwkv_time_mix)

AUX_LB_COEF = 0.01      # load-balance loss weight
AUX_Z_COEF = 0.001      # router z-loss weight

_BLOCK_SPECS = {"attn": attn_spec, "mla": mla_spec, "mamba": mamba_spec,
                "rwkv": rwkv_spec}


# ---------------------------------------------------------------------------
# Parameter tree
# ---------------------------------------------------------------------------
def _position_spec(cfg: ModelConfig, kind: str, mlp_kind: str, stacked: int):
    out = {"norm1": norm_spec(cfg, stacked),
           "block": _BLOCK_SPECS[kind](cfg, stacked)}
    if mlp_kind == "dense":
        out["norm2"] = norm_spec(cfg, stacked)
        out["mlp"] = mlp_spec(cfg, cfg.d_ff, stacked)
    elif mlp_kind == "moe":
        out["norm2"] = norm_spec(cfg, stacked)
        out["mlp"] = moe_spec(cfg, stacked)
    elif kind == "rwkv":
        out["norm2"] = norm_spec(cfg, stacked)   # channel-mix pre-norm
    return out


def model_spec(cfg: ModelConfig) -> Dict[str, Any]:
    g = cfg.n_groups
    spec: Dict[str, Any] = {
        "embed": embed_spec(cfg),
        "final_norm": norm_spec(cfg),
        "blocks": {
            f"pos{i}": _position_spec(cfg, kind, mlp_kind, g)
            for i, (kind, mlp_kind) in enumerate(
                zip(cfg.block_pattern, cfg.mlp_pattern))
        },
    }
    if cfg.first_layer_dense:
        first_kind = cfg.block_pattern[0]
        spec["layer0"] = {
            "norm1": norm_spec(cfg),
            "block": _BLOCK_SPECS[first_kind](cfg, 0),
            "norm2": norm_spec(cfg),
            "mlp": mlp_spec(cfg, cfg.d_ff_dense or cfg.d_ff, 0),
        }
    fe = frontend_spec(cfg)
    if fe:
        spec["frontend"] = fe
    return spec


# ---------------------------------------------------------------------------
# Embedding of model inputs
# ---------------------------------------------------------------------------
def embed_inputs(params, inputs: Dict[str, jax.Array], cfg: ModelConfig
                 ) -> jax.Array:
    """inputs: {"tokens": (B,S)} [+ "patches" (B,P,F) | "frames" (B,S,F)]."""
    if cfg.frontend == "audio":
        return embed_audio(params["frontend"], inputs["frames"], cfg)
    x = embed_tokens(params["embed"], inputs["tokens"], cfg)
    if cfg.frontend == "vision" and "patches" in inputs:
        prefix = embed_vision(params["frontend"], inputs["patches"], cfg)
        x = jnp.concatenate([prefix, x], axis=1)
    return x


# ---------------------------------------------------------------------------
# One block position (shared by train / prefill / decode bodies)
# ---------------------------------------------------------------------------
def _layer_state(stack, layer):
    """Layer ``layer``'s recurrent state, read from its stack."""
    return jax.tree_util.tree_map(lambda s: s[layer], stack)


def _write_state(stack, state, layer):
    """A recurrent layer's whole new state, written at its index of the
    stack (the other layers' states are not rewritten)."""
    return jax.tree_util.tree_map(
        lambda s, n: jax.lax.dynamic_update_index_in_dim(
            s, n.astype(s.dtype), layer, 0), stack, state)


def _apply_position(p, x, cfg: ModelConfig, kind: str, mlp_kind: str, *,
                    mode: str = "train", cache=None, pos=None, layer=0):
    """Returns (x, aux, new_cache_or_None).  ``aux`` is the (2,) MoE
    auxiliary losses in training; in serving (prefill / decode) a MoE
    position's routed experts per token (B, S, top_k) int32.  In decode,
    ``cache`` is the position's whole layer stack and ``layer`` its index
    there (and in the MoE expert stack); the stack comes back with only
    this layer's new state written."""
    rs = residual_scale(cfg)
    aux = jnp.zeros((2,), jnp.float32)
    new_cache = None

    h = apply_norm(p["norm1"], x, cfg)
    if kind == "attn":
        if mode == "decode":
            out, new_cache = attend_decode(p["block"], h, cache, pos, cfg,
                                           layer)
        elif mode == "prefill":
            out, (k, v) = attend_full(p["block"], h, cfg, return_kv=True)
            new_cache = (k, v)
        else:
            out = attend_full(p["block"], h, cfg)
    elif kind == "mla":
        if mode == "decode":
            out, new_cache = mla_decode(p["block"], h, cache, pos, cfg, layer)
        elif mode == "prefill":
            out, new_cache = mla_full(p["block"], h, cfg, return_cache=True)
        else:
            out = mla_full(p["block"], h, cfg)
    elif kind == "mamba":
        if mode == "decode":
            out, state = mamba_decode(p["block"], h,
                                      _layer_state(cache, layer), cfg)
            new_cache = _write_state(cache, state, layer)
        elif mode == "prefill":
            out, new_cache = mamba_full(p["block"], h, cfg, return_state=True)
        else:
            out = mamba_full(p["block"], h, cfg)
    elif kind == "rwkv":
        if mode == "decode":
            tlast, wkv, clast = _layer_state(cache, layer)
            out, (tlast2, wkv2) = rwkv_time_mix(
                p["block"], h, cfg, state=(tlast, wkv), return_state=True)
            x = x + out * rs
            h2 = apply_norm(p["norm2"], x, cfg)
            out2, clast2 = rwkv_channel_mix(p["block"], h2, cfg,
                                            last_x=clast, return_state=True)
            x = x + out2 * rs
            return x, aux, _write_state(cache, (tlast2, wkv2, clast2), layer)
        elif mode == "prefill":
            zs = init_rwkv_state(cfg, x.shape[0], cdtype(cfg))
            out, (tlast2, wkv2) = rwkv_time_mix(
                p["block"], h, cfg, state=(zs[0], zs[1]), return_state=True)
            x = x + out * rs
            h2 = apply_norm(p["norm2"], x, cfg)
            out2, clast2 = rwkv_channel_mix(p["block"], h2, cfg,
                                            last_x=zs[2], return_state=True)
            x = x + out2 * rs
            return x, aux, (tlast2, wkv2, clast2)
        else:
            out = rwkv_time_mix(p["block"], h, cfg)
            x = x + out * rs
            h2 = apply_norm(p["norm2"], x, cfg)
            x = x + rwkv_channel_mix(p["block"], h2, cfg) * rs
            return x, aux, None
    else:
        raise ValueError(f"unknown block kind {kind}")

    x = x + out * rs
    if mlp_kind != "none":
        h2 = apply_norm(p["norm2"], x, cfg)
        if mlp_kind == "moe" and mode == "train":
            m_out, aux = apply_moe(p["mlp"], h2, cfg)
        elif mlp_kind == "moe":
            m_out, aux = apply_moe_serve(p["mlp"], h2, cfg, layer)
        else:
            m_out = apply_mlp(p["mlp"], h2, cfg)
        x = x + m_out * rs
    x = constrain(x, "batch", "seq", None)
    return x, aux, new_cache


def _apply_layer0(params, x, cfg: ModelConfig, *, mode="train", cache=None,
                  pos=None):
    """deepseek's dense first layer (same block kind, dense MLP).  Its
    cache is unstacked; decode views it as a stack of one layer, so the
    same one-row write updates it in place."""
    p = params["layer0"]
    kind = cfg.block_pattern[0]
    rs = residual_scale(cfg)
    h = apply_norm(p["norm1"], x, cfg)
    new_cache = None
    if mode == "decode":
        decode = mla_decode if kind == "mla" else attend_decode
        out, stack = decode(p["block"], h,
                            jax.tree_util.tree_map(lambda c: c[None], cache),
                            pos, cfg, 0)
        new_cache = jax.tree_util.tree_map(lambda c: c[0], stack)
    elif kind == "mla":
        if mode == "prefill":
            out, new_cache = mla_full(p["block"], h, cfg, return_cache=True)
        else:
            out = mla_full(p["block"], h, cfg)
    elif mode == "prefill":
        out, (k, v) = attend_full(p["block"], h, cfg, return_kv=True)
        new_cache = (k, v)
    else:
        out = attend_full(p["block"], h, cfg)
    x = x + out * rs
    h2 = apply_norm(p["norm2"], x, cfg)
    x = x + apply_mlp(p["mlp"], h2, cfg) * rs
    return x, new_cache


# ---------------------------------------------------------------------------
# Full-sequence forward (train / encode)
# ---------------------------------------------------------------------------
_REMAT_POLICIES = {
    "dots": "dots_with_no_batch_dims_saveable",
    "full": "nothing_saveable",
}


def _gather_group_params(group_params, cfg: ModelConfig):
    """Explicit FSDP unshard: constrain every weight of this scan group to
    drop its "embed" (data-axis) sharding.  GSPMD then emits ONE all-gather
    per weight per group step (≈ params/n_groups bytes) and a backward
    reduce-scatter, instead of partial-sum all-reducing full activation
    tensors at every matmul — the classic ZeRO-3 forward schedule.  The
    gathers pipeline against the previous group's compute inside the scan.
    """
    from .params import logical_axes  # local: avoid import cycle at load

    dt = cdtype(cfg)

    def unshard(arr, ax):
        a = ax[1:] if (ax and ax[0] == "layers") else ax
        if "expert" in a:
            # EP is weight-stationary: tokens all-to-all to the experts;
            # gathering 16x expert weights per group would cost GiBs of
            # residency for nothing (measured on jamba train_4k, §Perf)
            return arr
        a = tuple(None if name == "embed" else name for name in a)
        # gather big matrices in the compute dtype: halves all-gather bytes
        # (fp32 master -> bf16 cast happens *before* the unshard constraint)
        if arr.ndim >= 2 and arr.dtype == jnp.float32 and cfg.dtype != "float32":
            arr = arr.astype(dt)
        return constrain(arr, *a)

    gathered = {}
    for i, (kind, mlp_kind) in enumerate(
            zip(cfg.block_pattern, cfg.mlp_pattern)):
        sub = group_params[f"pos{i}"]
        spec = _position_spec(cfg, kind, mlp_kind, stacked=1)
        arrs, tdef = jax.tree_util.tree_flatten(sub)
        axes = jax.tree_util.tree_leaves(
            logical_axes(spec), is_leaf=lambda x: isinstance(x, tuple))
        gathered[f"pos{i}"] = jax.tree_util.tree_unflatten(
            tdef, [unshard(a, ax) for a, ax in zip(arrs, axes)])
    return gathered


def _maybe_remat(fn, cfg: ModelConfig):
    """Per-layer-group remat: only the scan carry survives between groups;
    block internals are recomputed in backward per the policy.  This is what
    bounds train activation memory to O(1) in depth (EXPERIMENTS §Dry-run)."""
    if cfg.remat == "none":
        return fn
    policy = getattr(jax.checkpoint_policies, _REMAT_POLICIES[cfg.remat])
    return jax.checkpoint(fn, policy=policy)


def forward(params, inputs: Dict[str, jax.Array], cfg: ModelConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """-> (hidden (B, S, D), aux_losses (2,))."""
    x = embed_inputs(params, inputs, cfg)
    x = constrain(x, "batch", "seq", None)
    aux0 = jnp.zeros((2,), jnp.float32)
    if cfg.first_layer_dense:
        x, _ = _apply_layer0(params, x, cfg, mode="train")

    def group(x, group_params):
        if cfg.fsdp_gather_weights:
            group_params = _gather_group_params(group_params, cfg)
        aux = jnp.zeros((2,), jnp.float32)
        for i, (kind, mlp_kind) in enumerate(
                zip(cfg.block_pattern, cfg.mlp_pattern)):
            x, a, _ = _apply_position(group_params[f"pos{i}"], x, cfg,
                                      kind, mlp_kind, mode="train")
            aux = aux + a
        return x, aux

    group = _maybe_remat(group, cfg)

    def body(carry, group_params):
        x, aux = carry
        x, a = group(x, group_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["blocks"])
    x = apply_norm(params["final_norm"], x, cfg)
    return x, aux


def train_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """batch: {"tokens"/"frames", "labels", optional "mask"} → (loss, metrics)."""
    hidden, aux = forward(params, batch, cfg)
    logits = logits_from_hidden(params["embed"], hidden, cfg)
    labels = batch["labels"]
    mask = batch.get("mask")
    if logits.shape[1] != labels.shape[1]:        # vision prefix: no loss
        prefix = logits.shape[1] - labels.shape[1]
        logits = logits[:, prefix:]
    ce = cross_entropy(logits, labels, mask)
    loss = ce + AUX_LB_COEF * aux[0] + AUX_Z_COEF * aux[1]
    metrics = {"ce": ce, "load_balance": aux[0], "router_z": aux[1],
               "loss": loss}
    return loss, metrics


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
def _position_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    dtype, make):
    if kind == "attn":
        fns = {"init": init_kv_cache, "struct": kv_cache_struct}
        return fns[make](cfg, batch, max_len, dtype)
    if kind == "mla":
        fns = {"init": init_mla_cache, "struct": mla_cache_struct}
        return fns[make](cfg, batch, max_len, dtype)
    if kind == "mamba":
        fns = {"init": init_mamba_state, "struct": mamba_state_struct}
        return fns[make](cfg, batch, dtype)
    if kind == "rwkv":
        fns = {"init": init_rwkv_state, "struct": rwkv_state_struct}
        return fns[make](cfg, batch, dtype)
    raise ValueError(kind)


def _stack_struct(tree, n: int):
    return jax.tree_util.tree_map(
        lambda s: (jax.ShapeDtypeStruct((n,) + s.shape, s.dtype)
                   if isinstance(s, jax.ShapeDtypeStruct)
                   else jnp.broadcast_to(s, (n,) + s.shape)), tree)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, make: str = "init"):
    """Cache pytree: {"pos{i}": stacked-over-groups per-kind state}
    [+ "layer0" for deepseek].  ``make="struct"`` gives ShapeDtypeStructs."""
    cache: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        per = _position_cache(cfg, kind, batch, max_len, dtype, make)
        cache[f"pos{i}"] = _stack_struct(per, cfg.n_groups)
    if cfg.first_layer_dense:
        cache["layer0"] = _position_cache(cfg, cfg.block_pattern[0], batch,
                                          max_len, dtype, make)
    return cache


def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 dtype=jnp.bfloat16):
    return init_cache(cfg, batch, max_len, dtype, make="struct")


#: Logical sharding axes per cache-leaf kind (mirrors _position_cache).
_CACHE_AXES = {
    "attn": {"k": ("batch", "kv_heads", "kv_seq", None),
             "v": ("batch", "kv_heads", "kv_seq", None)},
    "mla": {"c_kv": ("batch", "kv_seq", None),
            "k_rope": ("batch", None, "kv_seq")},
    "mamba": (("batch", None, "mlp"), ("batch", None, "mlp")),
    "rwkv": (("batch", None), ("batch", "heads", None, None),
             ("batch", None)),
}


#: What each cache leaf holds, per block kind (mirrors _CACHE_AXES): the
#: attention families' keys and values or latents ("kv"), mamba's conv
#: window ("conv") and SSM state ("ssm"), rwkv's shifts and wkv state.
_CACHE_KINDS = {
    "attn": {"k": "kv", "v": "kv"},
    "mla": {"c_kv": "kv", "k_rope": "kv"},
    "mamba": ("conv", "ssm"),
    "rwkv": ("shift", "wkv", "shift"),
}


def cache_kinds(cfg: ModelConfig):
    """Pytree of kind names matching :func:`cache_struct` leaf for leaf."""
    out = {f"pos{i}": _CACHE_KINDS[kind]
           for i, kind in enumerate(cfg.block_pattern)}
    if cfg.first_layer_dense:
        out["layer0"] = _CACHE_KINDS[cfg.block_pattern[0]]
    return out


def cache_axes(cfg: ModelConfig):
    """Pytree of logical-axes tuples matching :func:`cache_struct` exactly
    (stacked positions gain a leading "layers" axis)."""
    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda ax: ("layers",) + ax, tree,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))

    out = {}
    for i, kind in enumerate(cfg.block_pattern):
        out[f"pos{i}"] = stacked(_CACHE_AXES[kind])
    if cfg.first_layer_dense:
        out["layer0"] = _CACHE_AXES[cfg.block_pattern[0]]
    return out


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def moe_layers(cfg: ModelConfig) -> int:
    """How many layers of the stack carry a MoE feed-forward."""
    return cfg.mlp_pattern.count("moe") * cfg.n_groups


def _serve_scan_xs(cfg: ModelConfig, blocks):
    """(scan xs, expert weights) for a serving scan over the layer groups:
    the MoE positions' routed-expert weights stay whole-stack, outside the
    scan, so that each group's grouped matmul reads its layer in place
    (``_serve_params``) instead of a per-group copy of all its experts."""
    xs, experts = {}, {}
    for i, mlp_kind in enumerate(cfg.mlp_pattern):
        pos = blocks[f"pos{i}"]
        if mlp_kind == "moe":
            mlp = dict(pos["mlp"])
            experts[f"pos{i}"] = {k: mlp.pop(k) for k in ("wi", "wg", "wo")}
            pos = dict(pos, mlp=mlp)
        xs[f"pos{i}"] = pos
    return (xs, jnp.arange(cfg.n_groups, dtype=jnp.int32)), experts


def _serve_params(group_params, experts, i: int):
    """Position ``i``'s params of one group, its experts whole-stack."""
    p = group_params[f"pos{i}"]
    if f"pos{i}" in experts:
        p = dict(p, mlp=dict(p["mlp"], **experts[f"pos{i}"]))
    return p


def _stack_experts(experts):
    """Per-position (n_groups, B, S, k) routed experts -> (MoE layers, B,
    S, k), in depth order."""
    return jnp.stack(experts, axis=1).reshape((-1,) + experts[0].shape[1:])


def prefill(params, inputs: Dict[str, jax.Array], cfg: ModelConfig,
            max_len: int, cache_dtype=jnp.bfloat16,
            return_experts: bool = False):
    """Process the prompt; -> (last-token logits (B, Vp), cache at S)
    [, each MoE layer's routed experts per token (L_moe, B, S, top_k)
    int32, global ids]."""
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no prefill/decode")
    x = embed_inputs(params, inputs, cfg)
    x = constrain(x, "batch", "seq", None)
    cache: Dict[str, Any] = {}
    if cfg.first_layer_dense:
        x, c0 = _apply_layer0(params, x, cfg, mode="prefill")
        cache["layer0"] = _pad_prefill(cfg, cfg.block_pattern[0], c0,
                                       max_len, cache_dtype)

    xs, experts = _serve_scan_xs(cfg, params["blocks"])

    def body(x, xs):
        group_params, layer = xs
        caches, routed = [], []
        for i, (kind, mlp_kind) in enumerate(
                zip(cfg.block_pattern, cfg.mlp_pattern)):
            x, r, c = _apply_position(
                _serve_params(group_params, experts, i), x, cfg, kind,
                mlp_kind, mode="prefill", layer=layer)
            caches.append(_pad_prefill(cfg, kind, c, max_len, cache_dtype))
            if mlp_kind == "moe":
                routed.append(r)
        return x, (tuple(caches), tuple(routed))

    x, (stacked, routed) = jax.lax.scan(body, x, xs)
    for i in range(cfg.period):
        cache[f"pos{i}"] = stacked[i]
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params["embed"], x[:, -1:], cfg)[:, 0]
    if return_experts:
        return logits, cache, _stack_experts(routed)
    return logits, cache


def _pad_prefill(cfg, kind, c, max_len, dtype):
    if kind == "attn":
        return cache_from_prefill(cfg, c[0], c[1], max_len, dtype)
    if kind == "mla":
        return mla_cache_from_prefill(cfg, c[0], c[1], max_len, dtype)
    return c    # mamba / rwkv states are O(1): stored as-is


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_step(params, cache, tokens: jax.Array, pos, cfg: ModelConfig,
                return_experts: bool = False):
    """One token for every sequence.  tokens (B,) int32; pos int32, one
    position for all rows (scalar) or each row's own (B,).

    The cache is updated in place: the layer stacks ride the layer scan's
    carry, and each layer writes only its B new rows (a recurrent layer its
    own state) at its index, so a donated cache is never rebuilt or copied.

    Returns (logits (B, Vp) fp32, updated cache) [, each MoE layer's
    routed experts (L_moe, B, 1, top_k) int32, global ids].
    """
    if cfg.is_encoder:
        raise ValueError(f"{cfg.name} is encoder-only: no decode step")
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), tokens.shape)
    x = embed_tokens(params["embed"], tokens[:, None], cfg)
    x = constrain(x, "batch", None, None)
    new_cache = {}
    if cfg.first_layer_dense:
        x, new_cache["layer0"] = _apply_layer0(
            params, x, cfg, mode="decode", cache=cache["layer0"], pos=pos)

    xs, experts = _serve_scan_xs(cfg, params["blocks"])

    def body(carry, xs):
        x, stacks = carry
        group_params, layer = xs
        stacks, routed = dict(stacks), []
        for i, (kind, mlp_kind) in enumerate(
                zip(cfg.block_pattern, cfg.mlp_pattern)):
            x, r, stacks[f"pos{i}"] = _apply_position(
                _serve_params(group_params, experts, i), x, cfg, kind,
                mlp_kind, mode="decode", cache=stacks[f"pos{i}"],
                pos=pos, layer=layer)
            if mlp_kind == "moe":
                routed.append(r)
        return (x, stacks), tuple(routed)

    stacks = {k: v for k, v in cache.items() if k != "layer0"}
    (x, stacks), routed = jax.lax.scan(body, (x, stacks), xs)
    new_cache.update(stacks)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = logits_from_hidden(params["embed"], x, cfg)[:, 0]
    if return_experts:
        return logits, new_cache, _stack_experts(routed)
    return logits, new_cache
