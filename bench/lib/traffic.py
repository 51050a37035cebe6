"""One generator for every traffic mix: a mix is a data file of
parameters (``traffic/<mix>.json``), never code.

The sizes and gaps of a mix are drawn once, from a fixed base seed, as a
sequence of ``pool`` requests. A run's ``--seed`` draws the prompt
tokens (and the weights) and nothing else: every seed brings the same
lengths and arrivals in the same order, so runs with different seeds
differ in content and not in the amount of work.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

BASE_SEED = 20240917


@dataclasses.dataclass(frozen=True)
class Item:
    index: int
    prompt_len: int
    output_len: int
    due_s: Optional[float]        # open loop: seconds after the window opens


def _lengths(rng, d: dict, n: int) -> np.ndarray:
    if d["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    x = np.exp(np.log(d["median"]) + d["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def _gaps(rng, d: dict, n: int, rate: float) -> np.ndarray:
    """Inter-arrival gaps with mean 1/rate."""
    if d["dist"] == "gamma":
        shape = 1.0 / d["cv"] ** 2
        return rng.gamma(shape, 1.0 / (shape * rate), n)
    if d["dist"] == "poisson":
        return rng.exponential(1.0 / rate, n)
    raise ValueError(f"unknown arrival distribution {d['dist']!r}")


def schedule(mix: dict, rate: Optional[float] = None) -> List[Item]:
    """The run's requests in order, the same for every seed. ``rate``
    (requests/s) is required for an open loop and ignored for a closed
    one."""
    base = np.random.default_rng(BASE_SEED)
    n = int(mix["pool"])
    prompts = _lengths(base, mix["prompt"], n)
    outputs = _lengths(base, mix["output"], n)
    due = [None] * n
    if mix["loop"] == "open":
        if not rate or rate <= 0:
            raise ValueError("an open-loop mix needs a positive rate")
        due = list(np.cumsum(_gaps(base, mix["arrivals"], n, 1.0)) / rate)
    elif mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [Item(i, int(prompts[i]), int(outputs[i]), due[i])
            for i in range(n)]


def prompt_tokens(seed: int, item: Item, vocab: int) -> np.ndarray:
    """The prompt of one request: uniform token ids, unshared."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), item.index])
    return rng.integers(0, vocab, item.prompt_len, dtype=np.int32)


def max_prompt(mix: dict) -> int:
    return int(mix["prompt"]["max"])


def min_prompt(mix: dict) -> int:
    return int(mix["prompt"]["min"])
