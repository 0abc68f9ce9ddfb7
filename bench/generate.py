"""The one traffic generator.  A traffic mix is a data file
(``traffic/<mix>.json``); this module turns it and a seed into requests.

Every seed gets the same amount of work: lengths and inter-arrival gaps
are the distribution's quantiles at (i + 0.5) / n, so a run's multiset of
sizes and gaps is fixed by the mix and the window, and the seed only
orders them (and draws the prompt tokens).  Planning problems are sorted
by a cost proxy into strata; every round takes one problem from each
stratum, cheap and dear alternating, so any stretch of the order carries
the same mix of work.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

from harness import sub_seed

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified draws of a lognormal (``median``, ``sigma`` of the
    log), clipped to [``min``, ``max``]."""
    q = [(i + 0.5) / n for i in range(n)]
    x = np.array([spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
                  for u in q])
    return np.clip(x, spec["min"], spec["max"])


def serve_requests(mix: dict, seed: int, seconds: float, *, vocab: int,
                   capacity: int) -> list[dict]:
    """Open-loop requests due in a window of ``seconds``: ``round(rate *
    seconds)`` arrivals whose gaps are exponential quantiles, with
    lognormal prompt and output lengths.  Prompt lengths round up to the
    mix's ``grid``; an output is cut where prompt plus output would not
    fit the engine's ``capacity``."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    p = mix["prompt_tokens"]
    grid = p["grid"]
    prompts = np.clip(np.ceil(lognormal_quantiles(p, n) / grid) * grid,
                      p["min"], p["max"]).astype(int)
    outs = np.round(lognormal_quantiles(mix["output_tokens"], n)).astype(int)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / mix["rate_per_s"]
                     for i in range(n)])
    rng = np.random.default_rng(sub_seed(seed, "serve"))
    prompts, outs, gaps = (rng.permutation(prompts), rng.permutation(outs),
                           rng.permutation(gaps))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    reqs = []
    for i in range(n):
        plen = int(prompts[i])
        reqs.append({
            "due": float(due[i]),
            "prompt": rng.integers(0, vocab, size=plen, dtype=np.int32),
            "max_new": int(max(1, min(outs[i], capacity - 1 - plen))),
        })
    return reqs


def zigzag(k: int) -> list[int]:
    """0, k-1, 1, k-2, ...: cheap and dear strata alternate."""
    lo, hi, out = 0, k - 1, []
    while lo <= hi:
        out.append(lo)
        if hi != lo:
            out.append(hi)
        lo, hi = lo + 1, hi - 1
    return out


def plan_order(costs, strata: int, seed: int, count: int):
    """``count`` (problem index, round) pairs.  Problems sorted by cost
    are split into ``strata`` groups; each group is permuted by the seed,
    and round r takes member r of every group in zigzag order."""
    idx = sorted(range(len(costs)), key=lambda i: (costs[i], i))
    groups = [g for g in np.array_split(np.array(idx), strata) if len(g)]
    rng = np.random.default_rng(sub_seed(seed, "order"))
    groups = [rng.permutation(g) for g in groups]
    out, r = [], 0
    while len(out) < count:
        for s in zigzag(len(groups)):
            g = groups[s]
            out.append((int(g[r % len(g)]), r))
            if len(out) == count:
                break
        r += 1
    return out
