"""Arithmetic of the measured window, over the harness's own records.

Every token a request receives is stamped with the host clock at the end
of the scheduler step that produced it (its first token with the
scheduler's admission stamp).  A rate is taken over all the tokens and
all the time of the window; a percentile over every sample in it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def tokens_in(stamps: Dict[int, List[float]], t0: float, t1: float) -> int:
    """Output tokens whose stamp lies in (t0, t1]."""
    return sum(1 for ts in stamps.values() for t in ts if t0 < t <= t1)


def rate(stamps: Dict[int, List[float]], t0: float, t1: float) -> float:
    """Tokens per second over the whole window."""
    return tokens_in(stamps, t0, t1) / (t1 - t0)


def gaps(stamps: Dict[int, List[float]], t0: float, t1: float
         ) -> List[float]:
    """Every gap between two consecutive tokens of one request that ends
    in (t0, t1]."""
    out = []
    for ts in stamps.values():
        for a, b in zip(ts, ts[1:]):
            if t0 < b <= t1:
                out.append(b - a)
    return out


def ttfts(submit: Dict[int, float], first: Dict[int, Optional[float]],
          t0: float, t1: float) -> List[float]:
    """Submit -> first token of every request submitted in [t0, t1)
    (its first token may come after t1)."""
    return [first[r] - s for r, s in submit.items()
            if t0 <= s < t1 and first.get(r) is not None]


def pct(values: Sequence[float], q: float) -> float:
    """The q-th percentile (linear interpolation between order stats)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def fp16_bytes(billed: float, billed_per_chunk: float,
               chunk_bytes: float) -> float:
    """Bytes that really moved for a billed figure: the store bills each
    chunk at ``billed_per_chunk`` (its codec-scaled size) while the fp16
    chunk of ``chunk_bytes`` crossed."""
    if billed_per_chunk <= 0:
        return 0.0
    return billed / billed_per_chunk * chunk_bytes
