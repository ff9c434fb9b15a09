"""The one traffic generator: turns a mix's parameter file into requests.

Every seed gets the same sequence of sizes and inter-arrival gaps, drawn
once with a fixed generator, started at its own place in that sequence (a
rotation drawn from the seed); the token contents of prompts are drawn from
the seed.  So two seeds offer the same amount of work with the same bursts,
each request keeping its size and the gap after it, and differ in where
the window starts within the sequence; an open loop starts it in a quiet
stretch, so that no burst is cut in two.  A permutation per seed would
offer the same amount of work too, but near the knee the tails then follow
how the seed happens to stack long requests into bursts, far more than
they follow the system.

A mix file holds:

* ``loop``: ``"open"`` (arrivals on a schedule, at the cell's ``rate``)
  or ``"closed"`` (``clients`` that each wait for their reply);
* ``arrivals`` (open loop): ``{"kind": "gamma", "cv": c, "starts": k}``,
  gaps with coefficient of variation ``c`` (1 is Poisson); a seed starts
  the sequence just after one of its ``k`` longest gaps (any place, if
  ``starts`` is left out);
* ``prompt`` / ``output``: a length law, ``{"kind": "lognormal",
  "median": m, "sigma": s}`` or ``{"kind": "uniform"}``, with optional
  ``min`` / ``max`` clips and ``round_up`` (the lengths a prompt is
  rounded up to; longer ones take the largest);
* ``block``: the length of a closed loop's sequence, which repeats;
* ``starts`` (closed loop): a seed starts the sequence at one of its first
  ``starts`` places (any place, if left out).  Which requests finish
  together, and so which prefills stall one gap between tokens, follows
  from where a closed loop starts, for the whole window.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import numpy as np

#: seed of the fixed generator that draws the shared sizes and gaps
SHARED_DRAW = 20250512


@dataclasses.dataclass
class Request:
    index: int
    due: float                 # seconds after the window opens (open loop)
    prompt_len: int = 0
    max_new: int = 0
    item: int = 0              # which pooled input (pipelines)


def _lengths(law: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = law.get("min", 1), law.get("max", None)
    if law["kind"] == "lognormal":
        x = law["median"] * np.exp(law["sigma"] * rng.standard_normal(n))
    elif law["kind"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length law {law['kind']!r}")
    x = np.clip(np.floor(x), lo, hi if hi is not None else np.inf)
    if "round_up" in law:
        steps = np.asarray(sorted(law["round_up"]))
        idx = np.minimum(np.searchsorted(steps, x), len(steps) - 1)
        x = steps[idx]
    return x.astype(np.int64)


def shared_sizes(mix: dict, n: int) -> tuple:
    """(prompt lengths, output lengths) of ``n`` requests, before any seed
    orders them."""
    rng = np.random.default_rng(SHARED_DRAW)
    prompt = (_lengths(mix["prompt"], n, rng) if "prompt" in mix
              else np.zeros(n, np.int64))
    output = (_lengths(mix["output"], n, rng) if "output" in mix
              else np.zeros(n, np.int64))
    return prompt, output


def prompt_lengths(mix: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    law = mix.get("prompt")
    if law is None:
        return []
    if "round_up" in law:
        return sorted(law["round_up"])
    raise ValueError("a prompt law without round_up sends lengths that "
                     "are not known in advance")


def open_schedule(mix: dict, seed: int, seconds: float,
                  rate: float) -> List[Request]:
    """``round(rate * seconds)`` requests due over ``[0, seconds)``."""
    n = max(1, int(round(rate * seconds)))
    prompt, output = shared_sizes(mix, n)
    arr = mix["arrivals"]
    if arr["kind"] != "gamma":
        raise ValueError(f"unknown arrival law {arr['kind']!r}")
    shape = 1.0 / arr["cv"] ** 2
    gaps = np.random.default_rng(SHARED_DRAW + 1).gamma(shape, 1.0, n)
    gaps *= (n / rate) / gaps.sum()
    # a start just after one of the longest gaps cuts no burst in two
    quiet = np.sort(np.argsort(gaps)[::-1][: arr.get("starts", n)] + 1) % n
    order = rotation(seed, n, quiet)
    due = np.concatenate([[0.0], np.cumsum(gaps[order][:-1])])
    return [Request(index=i, due=float(due[i]),
                    prompt_len=int(prompt[order[i]]),
                    max_new=int(output[order[i]]))
            for i in range(n)]


def rotation(seed: int, n: int, starts=None) -> np.ndarray:
    """The indices ``0 .. n-1`` of the shared sequence, started at one of
    ``starts`` (every place, by default) drawn from the seed."""
    starts = np.arange(n) if starts is None else np.asarray(starts)
    start = int(starts[np.random.default_rng(seed).integers(len(starts))])
    return (np.arange(n) + start) % n


def closed_stream(mix: dict, seed: int, n_items: int = 0
                  ) -> Iterator[Request]:
    """An endless stream of requests for a closed loop: the sequence of
    ``mix["block"]`` shared sizes, repeated, started where the seed's
    rotation says; ``item`` cycles through ``n_items`` pooled inputs in a
    seeded order."""
    block = int(mix.get("block", 64))
    prompt, output = shared_sizes(mix, block)
    order = rotation(seed, block, np.arange(mix.get("starts", block)))
    rng = np.random.default_rng([seed, 1])
    i = 0
    while True:
        items = rng.permutation(max(1, n_items))
        for j in range(block):
            yield Request(index=i, due=0.0, prompt_len=int(prompt[order[j]]),
                          max_new=int(output[order[j]]),
                          item=int(items[j % len(items)]))
            i += 1


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    """The token ids of request ``index``'s prompt, drawn from the seed."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab, length, dtype=np.int32)


def quantile(values, q: float) -> Optional[float]:
    """The ``q``-quantile (0..1) by linear interpolation; None if empty."""
    if len(values) == 0:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q))
