"""Serving steps: batched prefill and single-token decode.

``serve_step`` per the assignment: decode shapes lower ONE new token against
a KV cache of ``seq_len`` (decode_32k / long_500k), prefill shapes lower the
full-sequence prompt pass.  Encoder archs (hubert) expose ``encode`` — a
full forward returning per-frame logits — instead of prefill/decode.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from ..models.layers import logits_from_hidden
from ..models.transformer import decode_step, forward, prefill


def make_prefill_step(cfg: ModelConfig, max_len: int,
                      cache_dtype=jnp.bfloat16) -> Callable:
    if cfg.is_encoder:
        def encode(params, inputs):
            hidden, _ = forward(params, inputs, cfg)
            return logits_from_hidden(params["embed"], hidden, cfg)
        return encode

    def prefill_step(params, inputs):
        return prefill(params, inputs, cfg, max_len, cache_dtype)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    """One decode step: (params, cache, tokens, pos) -> (next tokens,
    logits, cache).  The decode engine takes its argmax inside its own
    step, so that no ``(B, vocab)`` logits ride its captured graph."""

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = decode_step(params, cache, tokens, pos, cfg)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, new_cache

    return serve_step


def greedy_generate(params, cfg: ModelConfig, prompt: jax.Array,
                    max_new: int, max_len: int) -> jax.Array:
    """Host-looped greedy decoding for the examples (prefill + N decodes)."""
    b, s = prompt.shape
    logits, cache = prefill(params, {"tokens": prompt}, cfg, max_len)
    step_fn = jax.jit(make_decode_step(cfg))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    for i in range(max_new - 1):
        tok, _, cache = step_fn(params, cache, tok, jnp.int32(s + i))
        out.append(tok)
    return jnp.stack(out, axis=1)
