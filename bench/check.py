"""The comparison that decides ``correct``: served tokens against the
plain reference.

For each sampled request the reference (``reference.py``: the dense
prompt pass, then LeoAM's sparse decode step by step) runs once over its
prompt and its served tokens; at every served position it gives the gap
by which the served token's logit lies below the reference's best logit
there.  The control instead puts first the token its own
(lower-precision) logits rank first.

Two numbers are compared, over every served token of the sampled
requests: the widest gap, which one altered token sets, and the median
gap, which a loss of precision across the tokens sets.  The widest gap
alone cannot tell the control from the program: where two chunks' box
bounds nearly tie, bf16 and f32 choose different chunks, which moves a
few tokens' logits by up to about 1.7 (PERF.md, section 6).
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

import gen_traffic
import reference


def sample(requests: Sequence[Any], k: int, seed: int) -> List[Any]:
    """``k`` requests drawn from the seed, always with the one that served
    the most tokens (ties: the longest prompt) among them."""
    reqs = sorted(requests, key=lambda r: r.rid)
    if len(reqs) <= k:
        return reqs
    longest = max(reqs, key=lambda r: (len(r.out), len(r.prompt)))
    rest = [r for r in reqs if r is not longest]
    rng = gen_traffic.rng_for(seed, 3)
    pick = rng.choice(len(rest), k - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


NUMBERS = ("logit_gap", "median_gap")


def numbers(per_token: Sequence[float]) -> Dict[str, float]:
    """The numbers compared, over every served token's gap."""
    v = np.asarray(per_token, np.float64)
    return {"logit_gap": float(v.max()), "median_gap": float(np.median(v))}


def gaps(conf: Dict[str, Any], params: Any, prompt: np.ndarray,
         served: Sequence[int], max_len: int,
         modes: Sequence[str] = ("f32",)) -> Dict[str, np.ndarray]:
    """Per served position: ``served`` -> the reference's best logit less
    its logit of the served token; ``<mode>`` (a control) -> the same for
    the token that mode's logits put first, over the same prompt and
    served tokens."""
    served = [int(t) for t in served]
    ref = reference.served_logits(conf, params, prompt, served, max_len)
    best = ref.max(-1)
    idx = np.arange(len(served))
    out = {"served": best - ref[idx, served]}
    for mode in modes:
        if mode == "f32":
            continue
        ctl = reference.served_logits(conf, params, prompt, served, max_len,
                                      mode)
        out[mode] = best - ref[idx, ctl.argmax(-1)]
    return out
