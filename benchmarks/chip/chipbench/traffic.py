"""The one traffic generator: reads a mix's parameters, makes its inputs.

Serving mixes are open loops.  Every seed gets the same multiset of sizes
and arrival gaps, in another order, so that seeds change which request
meets which and never how much work a run holds:

* ``n = round(rate_per_s * seconds)`` requests per block of ``seconds``;
* gaps are the exponential's quantiles at ``(i + 0.5) / n`` (a Poisson
  process's gaps, stratified), scaled to fill the block exactly;
* prompt and output lengths are a lognormal's quantiles at the same
  points, clipped to ``[min, max]``;
* the seed shuffles each list on its own and draws the prompt tokens.

With ``order_strata: k`` in the mix the seed does not shuffle a block
whole: a schedule drawn from the mix alone puts the gaps and lengths in
one order, and the seed moves each of them only among the ``k`` values
nearest it in rank.  Where a tail over a window's few dozen requests
turns on which large requests meet, this keeps the seed from changing
how much work meets at once.

Blocks repeat past the window (a fresh shuffle each), so the load stays
on while the window's requests drain.  Training mixes give each step its
own rows of uniform token ids.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class ServeRequest:
    rid: int
    due: float            # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the run's seed (any size) and a stream id."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at a lognormal's stratified quantiles, clipped."""
    z = np.asarray([NormalDist().inv_cdf(q) for q in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int, seconds: float) -> np.ndarray:
    g = -np.log1p(-quantiles(n))
    return g * (seconds / g.sum())


def block_size(mix: Dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def near_order(rng: np.random.Generator, base: np.random.Generator,
               values: np.ndarray, k: int) -> np.ndarray:
    """``values`` in the order ``base`` draws, each moved by ``rng`` only
    among the ``k`` values nearest it in rank."""
    n = len(values)
    rank = base.permutation(n)
    moved = rank.copy()
    for lo in range(0, n, k):
        slots = np.nonzero((rank >= lo) & (rank < lo + k))[0]
        moved[slots] = lo + rng.permutation(len(slots))
    return np.sort(values)[moved]


def serve_stream(mix: Dict, seed: int, seconds: float, vocab: int,
                 horizon: float) -> List[ServeRequest]:
    """Requests due in ``[0, horizon)``, blocks of ``seconds`` each."""
    n = block_size(mix, seconds)
    gaps0 = exponential_gaps(n, seconds)
    plen0 = lognormal_lengths(mix["prompt_len"], n)
    olen0 = lognormal_lengths(mix["output_len"], n)
    k = mix.get("order_strata")
    out: List[ServeRequest] = []
    for b in range(int(math.ceil(horizon / seconds))):
        rng = rng_for(seed, 1, b)
        if k is None:
            gaps = rng.permutation(gaps0)
            plen = rng.permutation(plen0)
            olen = rng.permutation(olen0)
        else:
            base = rng_for(0, 4, b)      # the mix's schedule, every seed's
            gaps = near_order(rng, base, gaps0, k)
            plen = near_order(rng, base, plen0, k)
            olen = near_order(rng, base, olen0, k)
        # the first request of a block is due at the block's start
        due = b * seconds + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for i in range(n):
            if due[i] >= horizon:
                break
            prompt = rng.integers(0, vocab, int(plen[i]), dtype=np.int64)
            out.append(ServeRequest(len(out), float(due[i]),
                                    prompt.astype(np.int32), int(olen[i])))
    return out


def train_rows(mix: Dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """``(batch, seq + 1)`` int32 token ids for one step; inputs are
    ``[:, :-1]`` and labels ``[:, 1:]``."""
    rng = rng_for(seed, 2, step)
    return rng.integers(0, vocab, (mix["batch"], mix["seq"] + 1),
                        dtype=np.int64).astype(np.int32)
