"""The benchmark's one traffic generator.

A traffic mix is a JSON file under ``bench/traffic/``; this module reads
its parameters and turns them, with the run's seed, into the stream of
requests a closed loop of clients sends.  Every seed gets the same
multiset of prompt lengths (``set_size`` of them, the law's quantiles), in
an order drawn from the seed, so two seeds do the same work; the prompt
tokens are drawn from the seed too.

Laws:

``uniform``          lengths evenly spaced over ``[lo, hi]``;
``zipf_geometric``   a zipf(``a``) rank, capped at ``rank_cap``, mapped
                     geometrically onto ``[lo, hi]`` (rank 1 at ``lo``,
                     the capped tail at ``hi``) -- the length law of
                     ``repro.serving.trace``.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of the run's seed (any
    whole number, negative or past 64 bits included)."""
    s = int(seed)
    words = [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, int(s < 0), stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def _zipf_rank_quantiles(a: float, cap: int, n: int) -> List[int]:
    """The ranks at the ``n`` mid-quantiles of zipf(a) capped at ``cap``
    (the tail mass past ``cap`` lands on ``cap``)."""
    k = np.arange(1, 200_000, dtype=np.float64)
    pk = k ** -a
    pk /= pk.sum()
    p = np.concatenate([pk[: cap - 1], [pk[cap - 1:].sum()]])
    cdf = np.cumsum(p)
    qs = (np.arange(n) + 0.5) / n
    return [int(np.searchsorted(cdf, q)) + 1 for q in qs]


def length_set(law: Dict, n: int) -> List[int]:
    """The ``n`` prompt lengths every seed of this mix uses."""
    lo, hi = int(law["lo"]), int(law["hi"])
    if law["law"] == "uniform":
        return [int(round(lo + (hi - lo) * (i + 0.5) / n)) for i in range(n)]
    if law["law"] == "zipf_geometric":
        cap = int(law.get("rank_cap", 64))
        ranks = _zipf_rank_quantiles(float(law["a"]), cap, n)
        return [int(round(lo * (hi / lo) ** ((r - 1) / (cap - 1))))
                for r in ranks]
    raise ValueError(f"unknown prompt-length law {law['law']!r}")


@dataclass
class Spec:
    """One request: its prompt tokens and how many tokens it asks for."""
    prompt: np.ndarray
    max_new: int


def stream(mix: Dict, vocab: int, seed: int) -> Iterator[Spec]:
    """Requests in the order the clients send them: the length multiset
    in a seeded order, cycled (each cycle in a fresh order)."""
    lengths = length_set(mix["prompt"], int(mix["set_size"]))
    order_rng = rng_for(seed, 1)
    tok_rng = rng_for(seed, 2)
    max_len = int(mix["max_len"])
    while True:
        for L in order_rng.permutation(lengths):
            L = int(L)
            if mix["max_new"] == "fill":
                # a session decodes until its cache is one token short
                # of max_len
                max_new = max_len - L - 1
            else:
                max_new = int(mix["max_new"])
            yield Spec(prompt=tok_rng.integers(2, vocab, L).astype(np.int64),
                       max_new=max_new)
