"""Length and token arithmetic shared by the generators.

Lengths are lognormal with a median and a sigma, clipped, as
``repro.serving.traffic._clipped_lognormal`` draws them; here they are drawn
as stratified quantiles, so that every seed gets the same multiset of sizes
in another order and a seed changes the order of the work, not its amount.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, count: int) -> np.ndarray:
    """``count`` stratified draws of a clipped lognormal: the quantiles at
    (j + 0.5) / count of the distribution with median ``spec["median"]`` and
    log-sigma ``spec["sigma"]``, rounded and clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((j + 0.5) / count)
                  for j in range(count)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(mean: float, count: int) -> np.ndarray:
    """``count`` stratified draws of an exponential with mean ``mean``."""
    p = (np.arange(count) + 0.5) / count
    return -mean * np.log1p(-p)


def prompt_lengths(spec: dict, count: int) -> np.ndarray:
    """Retrieval-shaped prompts: an instruction of fixed length plus a
    number of equal chunks (``spec["chunks"]`` is the count's lognormal)."""
    k = lognormal_quantiles(spec["chunks"], count)
    return spec["instruction_tokens"] + k * spec["chunk_tokens"]


@dataclasses.dataclass(eq=False)
class Ask:
    """One request as the generator sends it: ``arrival`` is the scheduled
    send time in window seconds (None for requests sent during set-up)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: float | None = None
    client: int = -1


def sized_asks(params: dict, rng: np.random.Generator, count: int,
               vocab: int, first_rid: int = 0,
               tokens: np.random.Generator | None = None) -> list:
    """``count`` requests whose prompt and output lengths are the
    stratified multisets of ``params``, each shuffled by ``rng``, with
    prompt token ids drawn uniformly over the vocabulary by ``tokens``
    (``rng`` when not given)."""
    tokens = rng if tokens is None else tokens
    prompts = rng.permutation(prompt_lengths(params["prompt"], count))
    outputs = rng.permutation(lognormal_quantiles(params["output"], count))
    return [Ask(first_rid + j,
                tokens.integers(0, vocab, int(p), dtype=np.int64)
                .astype(np.int32), int(o))
            for j, (p, o) in enumerate(zip(prompts, outputs))]


def prompt_shapes(asks) -> list:
    """The distinct prompt lengths of ``asks``: the shapes warm-up
    compiles."""
    return sorted({len(a.prompt) for a in asks})


def max_reach(asks) -> int:
    """The widest prompt + output + 1 of ``asks`` (the cache depth a
    deployment needs for them)."""
    return max(len(a.prompt) + a.max_new_tokens + 1 for a in asks)
